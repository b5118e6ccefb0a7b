"""Kind ``roster``: one cold pass of a suite roster through ``SuiteRunner``
(``roster()`` -> ``simulate_many`` -> the device window scan).

The configuration names the program's registry (``registry``: ``default``
for ``repro.suite.default_registry``, ``serving`` for
``serving_registry``) and lists its entries in order, each with the class
it states.  An entry with a ``family`` is one of the reference's synthetic
DAMOV families: the reference makes its traces from the configuration
alone.  Any other entry (a captured kernel, a serving scenario) has no
generator outside the program, so the reference is fed the program's
traces of it, with their LLC share, arithmetic intensity and instructions
per reference, and simulates and classifies them itself.

The traces come from the configuration's fixed ``trace_seed`` (the CLI's
default); ``--seed`` permutes the order in which the roster is registered.
Every seed thus runs the same traces and compiles the same scan shapes.
"""

from __future__ import annotations

import numpy as np

from bench import reference as ref
from bench.jobs import Number, annotate, differing


class Job:
    def __init__(self, config: dict, traffic: dict, seed: int) -> None:
        self.config, self.traffic, self.seed = config, traffic, seed
        self.cores = tuple(config["core_sweep"])
        self.entries = config["entries"]
        rng = np.random.default_rng(seed)
        self.order = [int(i) for i in rng.permutation(len(self.entries))]
        self._last = None

    def _registry(self):
        import repro.suite.registry as registries

        build = getattr(registries, f"{self.config['registry']}_registry",
                        None)
        if build is None:
            raise SystemExit(f"bench: the program has no registry "
                             f"{self.config['registry']!r}")
        return build(refs=self.config["refs"])

    def setup(self) -> None:
        """Check that the program's roster is the configuration's."""
        reg = self._registry()
        got = [(e.name, e.source, e.expected_class) for e in reg]
        want = [(e["name"], e["source"], e["expected"]) for e in self.entries]
        if got != want:
            raise SystemExit("bench: the program's roster differs from "
                             "the configuration file's")
        by_name = {e.name: e for e in reg}
        for e in self.entries:
            if "family" not in e:
                continue
            entry = by_name[e["name"]]
            if (dict(entry.params) != e["params"]
                    or entry.workload.ai_ops_per_access != e["ai"]
                    or entry.workload.instr_per_access != e["instr_per_ref"]):
                raise SystemExit(f"bench: roster entry {e['name']} differs "
                                 f"from the configuration file's")

    def _runner(self):
        from repro.capture import jaxpr as capture_jaxpr
        from repro.core import cachesim_vec
        from repro.suite import SuiteRunner
        from repro.suite.registry import SuiteRegistry

        capture_jaxpr.clear_memo()
        cachesim_vec.clear_memo()
        built = list(self._registry())
        reg = SuiteRegistry(entries=[built[i] for i in self.order],
                            refs=self.config["refs"])
        return reg, SuiteRunner(reg, seed=self.config["trace_seed"],
                                cores=self.cores,
                                backend=self.traffic["backend"], store=None)

    def job(self) -> dict:
        from repro.core import cachesim

        with annotate("bench.registry"):
            reg, runner = self._runner()
        with annotate("bench.roster"):
            rows = runner.roster().records()
        engine = runner.study.engine
        counters = {}
        for entry in reg:
            for c in self.cores:
                sim = engine.simulate(entry.workload, c,
                                      cachesim.host_config(c),
                                      seed=self.config["trace_seed"])
                counters[(entry.name, c)] = (tuple(sim.level_hits),
                                             tuple(sim.level_misses))
        self._last = (reg, runner)
        return {"classes": {r["name"]: r["assigned"] for r in rows},
                "counters": counters}

    def count_refs(self) -> int:
        """Trace references one job characterizes, each trace once."""
        reg, runner = self._last
        seen, total = set(), 0
        for entry in reg:
            for c in self.cores:
                addr = runner.study.engine.trace(
                    entry.workload, c, seed=self.config["trace_seed"]).addresses
                if id(addr) not in seen:
                    seen.add(id(addr))
                    total += int(addr.size)
        return total

    def last_outputs(self) -> dict:
        """The last job's traces of every entry at every core count, with
        their LLC share, and each entry's arithmetic intensity and
        instructions per reference; then the job's state is dropped."""
        reg, runner = self._last
        traces, rates = {}, {}
        for entry in reg:
            w = entry.workload
            rates[entry.name] = (w.ai_ops_per_access, w.instr_per_access)
            for c in self.cores:
                spec = runner.study.engine.trace(
                    w, c, seed=self.config["trace_seed"])
                traces[(entry.name, c)] = (np.asarray(spec.addresses),
                                           float(spec.l3_factor))
        self._last = None
        return {"traces": traces, "rates": rates}

    def placement(self) -> dict:
        """:meth:`last_outputs` of a roster built for it alone, with no
        simulation (the control needs the fed traces and nothing else)."""
        self._last = self._runner()
        return self.last_outputs()

    def reference(self, last: dict, level=ref.lru_level) -> dict:
        """Every entry's counters and class, and the synthetic entries'
        traces, by the reference (fed ``last``'s traces where an entry has
        no generator here)."""
        cfg = self.config
        levels = [tuple(x) for x in cfg["hierarchy"]["levels"]]
        out = {"classes": {}, "counters": {}, "traces": {}}
        memo: dict = {}
        for e in self.entries:
            name = e["name"]
            if "family" in e:
                ai, ipr = e["ai"], e["instr_per_ref"]
            else:
                ai, ipr = last["rates"][name]
            lfmr, mpki, one_core = [], {}, None
            for c in self.cores:
                if "family" in e:
                    addr, share = ref.synthetic_trace(e, c, cfg["trace_seed"])
                    out["traces"][(name, c)] = addr
                else:
                    addr, share = last["traces"][(name, c)]
                if one_core is None:
                    one_core = addr
                hits, misses = ref.simulate(addr, levels, share=share,
                                            level=level, memo=memo)
                out["counters"][(name, c)] = (hits, misses)
                lfmr.append(misses[-1] / misses[0] if misses[0] else 0.0)
                instr = int(round(addr.size * max(1.0, ipr)))
                mpki[c] = 1000.0 * misses[-1] / instr if instr else 0.0
            temporal = ref.temporal_locality(one_core, cfg["locality_window"])
            near = min(self.cores, key=lambda c: abs(c - cfg["mpki_cores"]))
            out["classes"][name] = ref.classify(
                temporal, ai, mpki[near], lfmr[-1] - lfmr[0],
                cfg["thresholds"])
        return out

    def compare(self, jobs: list[dict], last: dict, want: dict) -> list[Number]:
        traces = 0
        for key, addr in want["traces"].items():
            got = last["traces"].get(key, (np.empty(0, dtype=np.int64),))[0]
            n = min(got.size, addr.size)
            traces += int(np.count_nonzero(got[:n] != addr[:n]))
            traces += abs(int(got.size) - int(addr.size))
        stated = {e["name"]: e["expected"] for e in self.entries}
        counters = classes = unstated = 0
        for answers in jobs:
            for key, (hits, misses) in want["counters"].items():
                got = answers["counters"].get(key, ((), ()))
                counters += differing(got[0], hits) + differing(got[1],
                                                                misses)
            for name, cls in want["classes"].items():
                assigned = answers["classes"].get(name)
                classes += int(assigned != cls)
                unstated += int(assigned != stated[name])
        return [Number("trace_refs_differing", traces, 0),
                Number("counters_differing", counters, 0),
                Number("classes_differing", classes, 0),
                Number("classes_not_as_stated", unstated, 0)]
