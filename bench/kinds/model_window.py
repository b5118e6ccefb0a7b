"""Kind ``model_window``: one fresh abstract capture of a model step, a
centred window of its trace walked op by op (``walk_stream``) and streamed
through ``simulate_chunked`` once per hierarchy.

The configuration names the program's model configuration
(``program_config``) and maps each of its fields to the key of the
configuration file that states it (``program_fields``); set-up builds the
model from those numbers and refuses to run if one did not take.  The
traffic file gives the step (``mode``; ``decode`` is the one made here),
its batch and cache, the window and the hierarchies.

``--seed`` draws an offset added to every address, a multiple of
``offset_align_words`` (one span of the largest set count's lines): each
run's addresses are its own, while every cache set sees the same stream of
the same set index, so the work, and every scan shape, is the same.
"""

from __future__ import annotations

import zlib

import numpy as np

from bench import reference as ref
from bench.jobs import Number, annotate, differing


def _decode(lm, traffic: dict):
    """``LM.decode_step`` over a cache of ``cache_len`` for ``batch``
    sequences: the step and its abstract arguments."""
    import jax
    import jax.numpy as jnp

    params = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: lm.init_cache(traffic["batch"],
                                                 traffic["cache_len"]))
    toks = jax.ShapeDtypeStruct((traffic["batch"], 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((traffic["batch"],), jnp.int32)
    return (lambda p, x, c, po: lm.decode_step(p, x, c, po),
            (params, toks, cache, pos))


STEPS = {"decode": _decode}


class Job:
    def __init__(self, config: dict, traffic: dict, seed: int) -> None:
        self.config, self.traffic, self.seed = config, traffic, seed
        if traffic["mode"] not in STEPS:
            raise SystemExit(f"bench: mode {traffic['mode']!r} is not made "
                             f"here (only {sorted(STEPS)})")
        rng = np.random.default_rng(seed)
        self.offset = (int(traffic["offset_align_words"])
                       * int(rng.integers(1, int(traffic["offset_steps"]))))
        self._last = None

    def setup(self) -> None:
        """Build the program's model configuration from the file's
        numbers, and check that every one of them took."""
        from repro import configs
        from repro.models.model import LM

        c = self.config
        stated = {field: c[key] for field, key in c["program_fields"].items()}
        model = configs.get(c["program_config"]).replace(**stated)
        for field, value in stated.items():
            if getattr(model, field) != value:
                raise SystemExit(f"bench: model {field}="
                                 f"{getattr(model, field)} but the "
                                 f"configuration states {value}")
        self.lm = LM(model)

    def _capture(self):
        from repro.capture.model import capture_model

        fn, args = STEPS[self.traffic["mode"]](self.lm, self.traffic)
        return capture_model(fn, args, name=self.config["name"])

    def _hierarchy(self, name: str):
        from repro.core import cachesim

        make = getattr(cachesim, f"{name}_config", None)
        if make is None:
            raise SystemExit(f"bench: the program has no hierarchy {name!r}")
        return make(self.traffic["cores"])

    def job(self) -> dict:
        from repro import obs
        from repro.core.cachesim_stream import simulate_chunked

        t = self.traffic
        with annotate("bench.capture"):
            mc = self._capture()
        counters, digests = {}, {}
        for name in t["hierarchies"]:
            digest = [0, 0]

            def feed(blocks, digest=digest):
                for blk in blocks:
                    with obs.span("bench.feed"):
                        moved = blk + self.offset
                        digest[0] = zlib.crc32(moved, digest[0])
                        digest[1] += int(moved.size)
                    yield moved

            with annotate("bench.simulate"):
                sim = simulate_chunked(
                    feed(mc.walk_stream(t["window_refs"],
                                        center=t["center"])),
                    self._hierarchy(name), scan=t["scan"])
            counters[name] = (tuple(sim.level_hits), tuple(sim.level_misses))
            digests[name] = tuple(digest)
        self._last = mc
        return {"counters": counters, "digests": digests}

    def _window(self, total: int) -> tuple[int, int]:
        """Where the window lies in a step of ``total`` references: the
        whole step where it is shorter than the window."""
        t = self.traffic
        if total <= t["window_refs"]:
            return 0, total
        start = int((total - t["window_refs"])
                    * min(max(t["center"], 0.0), 1.0))
        return start, start + t["window_refs"]

    def count_refs(self) -> int:
        lo, hi = self._window(sum(op.walk(count_only=True).refs
                                  for op in self._last.ops))
        return hi - lo

    def placement(self) -> dict:
        """:meth:`last_outputs` of a capture made for it alone (the
        control needs the placement and nothing the window produces)."""
        self._last = self._capture()
        return self.last_outputs()

    def last_outputs(self) -> dict:
        """Where the last job's window lies: for each op of the step its
        kind, length, operand shapes, elements per word and base addresses;
        and, for each op in the window of a kind the reference does not
        generate, the program's words of it there."""
        mc = self._last
        counts = [op.walk(count_only=True).refs for op in mc.ops]
        start, end = self._window(sum(counts))
        ops, fed, pos = [], {}, 0
        for i, (op, n) in enumerate(zip(mc.ops, counts)):
            ops.append({"kind": op.kind, "refs": n, "bases": dict(op.bases),
                        "operands": [{"name": o.name, "role": o.role,
                                      "shape": tuple(o.shape),
                                      "epw": o.elems_per_word}
                                     for o in op.capture.operands]})
            if (op.kind not in ref.OP_WALKS and pos < end
                    and pos + n > start):
                fed[i] = np.array(op.walk().addresses[
                    max(0, start - pos):end - pos])
            pos += n
        self._last = None
        return {"ops": ops, "window": (start, end), "fed": fed}

    def reference(self, last: dict, level=ref.lru_level) -> dict:
        """The window's words, generated op by op from the placement, and
        each hierarchy's counters over them."""
        words = ref.window_words(last["ops"], *last["window"], last["fed"])
        words = words + self.offset
        out = {"counters": {}, "digests": {}}
        for name in self.traffic["hierarchies"]:
            levels = [tuple(x)
                      for x in self.config["hierarchies"][name]["levels"]]
            out["counters"][name] = ref.simulate(words, levels, level=level)
            out["digests"][name] = (zlib.crc32(words), int(words.size))
        return out

    def compare(self, jobs: list[dict], last: dict, want: dict) -> list[Number]:
        traces = counters = 0
        for answers in jobs:
            for name in self.traffic["hierarchies"]:
                traces += int(answers["digests"].get(name)
                              != want["digests"][name])
                got = answers["counters"].get(name, ((), ()))
                hits, misses = want["counters"][name]
                counters += differing(got[0], hits) + differing(got[1],
                                                                misses)
        return [Number("window_digests_differing", traces, 0),
                Number("counters_differing", counters, 0)]
