"""The benchmark's correctness check, at sizes a test run can hold.

- The control (the reference with a shortcut scan in place of exact LRU)
  must come out not correct, in both kinds of cell.
- A whole run, with the look for a chip skipped, must come out correct on
  the program as it is, and not correct with each fault the cells can
  have planted under the timed path: an answer altered where it is
  produced, the scan's state returned unchanged, half of the work left
  out, and the captured trace altered.
- Without a TPU the run ends with an error and prints no result.
"""

from __future__ import annotations

import dataclasses
import json

import numpy as np
import pytest

from bench import control, manifest, reference, run

SMALL_REFS = 20_000
# two captured kernels of the roster, the smallest of its traces
SMALL_CAPTURED = ("pal.moe.warm.8e", "pal.ssm.expand.512.d128")


def _roster_cell() -> manifest.Cell:
    """``table3.r250k`` with its 21 synthetic entries at 20 000 refs and
    two of its captured kernels."""
    cell = manifest.cell("table3.r250k")
    cfg = json.loads(json.dumps(cell.config))
    cfg["refs"] = SMALL_REFS
    cfg["entries"] = [e for e in cfg["entries"]
                      if "family" in e or e["name"] in SMALL_CAPTURED]
    for e in cfg["entries"]:
        if "family" in e:
            e["params"]["refs"] = SMALL_REFS
            if "trace_refs" in e["params"]:
                e["params"]["trace_refs"] = 2 * SMALL_REFS
    return dataclasses.replace(cell, config=cfg)


def _window_cell() -> manifest.Cell:
    """The decode-window configuration and traffic at narrow widths (built
    from their files): the centred window of 2^18 refs still lies in the
    FFN gate and up projections."""
    cfg = json.loads(manifest.config_path("qwen2.5-14b-decode").read_text())
    cfg.update(hidden_size=512, intermediate_size=2048, num_attention_heads=8,
               num_key_value_heads=2, vocab_size=1024)
    traffic = json.loads(manifest.traffic_path("decode.bs64c4k").read_text())
    traffic.update(batch=16, cache_len=128, window_refs=1 << 18)
    m = manifest.load_manifest()
    return manifest.Cell("decode.window.small", 1, cfg, traffic,
                         tuple(m["end_to_end"]), tuple(m["per_layer"]))


def _whole_step_cell(model: str) -> manifest.Cell:
    """A new cell made of data alone: a tiny decode step of ``model``
    streamed whole (a window longer than the step), so every op of the
    step, matmuls and whole-array ops, is in it."""
    qwen = json.loads(manifest.config_path("qwen2.5-14b-decode").read_text())
    if model == "qwen":
        cfg = dict(qwen, hidden_size=256, intermediate_size=512,
                   num_attention_heads=4, num_key_value_heads=2,
                   vocab_size=512)
    else:
        cfg = {"name": "mamba2-tiny", "program_config": "mamba2-780m",
               "program_fields": {"d_model": "hidden_size",
                                  "n_layers": "num_hidden_layers",
                                  "vocab": "vocab_size",
                                  "ssm_state": "state_size",
                                  "ssm_head_dim": "head_dim",
                                  "ssm_expand": "expand"},
               "hidden_size": 128, "num_hidden_layers": 1,
               "vocab_size": 512, "state_size": 16, "head_dim": 16,
               "expand": 2, "hierarchies": qwen["hierarchies"]}
    traffic = json.loads(manifest.traffic_path("decode.bs64c4k").read_text())
    traffic.update(batch=8, cache_len=64, window_refs=1 << 30)
    m = manifest.load_manifest()
    return manifest.Cell(f"{model}.whole_step.tiny", 1, cfg, traffic,
                         tuple(m["end_to_end"]), tuple(m["per_layer"]))


@pytest.fixture
def roster_registry(monkeypatch):
    """The program's default roster cut to the entries of
    :func:`_roster_cell`."""
    import repro.suite.registry as registries

    real = registries.default_registry

    def cut(*, refs=None):
        reg = real(refs=refs)
        return registries.SuiteRegistry(
            entries=[e for e in reg if e.source == "synthetic"
                     or e.name in SMALL_CAPTURED], refs=refs)

    monkeypatch.setattr(registries, "default_registry", cut)


def _run(monkeypatch, capsys, cell, out_dir, seed=3_000_000_019,
         trace=0) -> dict:
    monkeypatch.setattr(run.manifest, "cell", lambda name: cell)
    monkeypatch.setattr(run, "check_device", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    monkeypatch.setattr(run, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(run, "device_memory_peak", lambda chips: 0)
    monkeypatch.setattr(run, "OUT", out_dir)
    assert run.main(["--workload", cell.name, "--seed", str(seed),
                     "--seconds", "0.01", "--trace", str(trace)]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


# --------------------------------------------------------------------------
# The control.
# --------------------------------------------------------------------------
@pytest.mark.parametrize("seed", [1, 2, 3_000_000_021])
def test_control_is_not_correct_on_the_roster(seed, roster_registry):
    out = control.control(_roster_cell(), seed)
    assert out["correct"] is False
    assert out["numbers"]["trace_refs_differing"]["value"] == 0
    assert out["numbers"]["counters_differing"]["value"] > 0


@pytest.mark.parametrize("seed", [1, 3_000_000_023])
def test_control_is_not_correct_on_the_window(seed):
    out = control.control(_window_cell(), seed)
    assert out["correct"] is False
    assert out["numbers"]["window_digests_differing"]["value"] == 0
    assert out["numbers"]["counters_differing"]["value"] > 0


def test_lru_level_is_least_recently_used():
    # 2 ways, one set: a b a c -> c evicts b (a was used more recently)
    hits, misses = reference.lru_level([1, 2, 1, 3, 1, 2], sets=1, ways=2)
    assert hits == 2 and misses == [1, 2, 3, 2]
    # the control counts repeats (2 2 fills both ways) and caps the window
    hits, misses = reference.approximate_level([1, 2, 2, 1], sets=1, ways=2)
    assert hits == 1 and misses == [1, 2, 1]
    hits, misses = reference.approximate_level([1, 2, 1, 3, 1], sets=1,
                                               ways=2, window=1)
    assert hits == 0


# --------------------------------------------------------------------------
# Whole runs with the timed path broken underneath.
# --------------------------------------------------------------------------
def _alter_answer(monkeypatch):
    from repro.core import cachesim_stream, cachesim_vec

    def bump(sim):
        sim.level_hits = (sim.level_hits[0] + 1,) + tuple(sim.level_hits[1:])
        return sim

    many, chunked = cachesim_vec.simulate_many, cachesim_stream.simulate_chunked
    monkeypatch.setattr(cachesim_vec, "simulate_many", lambda *a, **k: [
        [bump(s) for s in sims] for sims in many(*a, **k)])
    monkeypatch.setattr(cachesim_stream, "simulate_chunked",
                        lambda *a, **k: bump(chunked(*a, **k)))


def _captured_answer_altered(monkeypatch):
    from repro.study.engine import SimEngine

    cells = SimEngine.simulate_cells

    def altered(self, items, **k):
        items = list(items)
        sims = cells(self, items, **k)
        for (w, _, _), sim in zip(items, sims):
            if w.name in SMALL_CAPTURED and not getattr(sim, "bench_fault",
                                                       False):
                sim.level_misses = ((sim.level_misses[0] + 1,)
                                    + tuple(sim.level_misses[1:]))
                sim.bench_fault = True
        return sims

    monkeypatch.setattr(SimEngine, "simulate_cells", altered)


def _scan_state_unchanged(monkeypatch):
    from repro.core import cachesim_vec

    monkeypatch.setattr(cachesim_vec, "_jax_window_counts",
                        lambda kern, q, lo, thr, span, chunk:
                        np.zeros(len(lo), dtype=np.int64))


def _half_left_out(monkeypatch):
    from repro.capture.model import ModelCapture
    from repro.core import cachesim_vec

    many, walk = cachesim_vec.simulate_many, ModelCapture.walk_stream
    monkeypatch.setattr(cachesim_vec, "simulate_many", lambda reqs, **k: many(
        [(a[:a.size // 2], c, o) for a, c, o in reqs], **k))

    def half(self, *a, **k):
        blocks = list(walk(self, *a, **k))
        return iter(blocks[:len(blocks) // 2])

    monkeypatch.setattr(ModelCapture, "walk_stream", half)


def _trace_altered(monkeypatch):
    from repro.capture.model import ModelCapture
    from repro.study.engine import SimEngine

    trace, walk = SimEngine.trace, ModelCapture.walk_stream

    def altered_trace(self, workload, cores, *, seed=0):
        spec = trace(self, workload, cores, seed=seed)
        if not getattr(spec, "bench_fault", False):
            spec.addresses[0] += 8
            spec.bench_fault = True
        return spec

    def altered_walk(self, *a, **k):
        for i, blk in enumerate(walk(self, *a, **k)):
            if i == 0:
                blk = blk.copy()
                blk[0] += 8
            yield blk

    monkeypatch.setattr(SimEngine, "trace", altered_trace)
    monkeypatch.setattr(ModelCapture, "walk_stream", altered_walk)


FAULTS = {"answer_altered": _alter_answer,
          "scan_state_unchanged": _scan_state_unchanged,
          "half_left_out": _half_left_out,
          "trace_altered": _trace_altered}
ROSTER_FAULTS = dict(FAULTS, captured_answer_altered=_captured_answer_altered)


def test_roster_run_is_correct(monkeypatch, capsys, roster_registry,
                               tmp_path):
    out = _run(monkeypatch, capsys, _roster_cell(), tmp_path)
    assert out["correct"] is True, out["check"]
    assert set(out["metrics"]) == {"sim_refs_per_s", "setup_s"}
    assert list(out)[-1] == "check"


def test_roster_traced_run_reads_span_metrics(monkeypatch, capsys,
                                              roster_registry, tmp_path):
    out = _run(monkeypatch, capsys, _roster_cell(), tmp_path, trace=1)
    assert out["correct"] is True
    assert {"capture.walk_s_per_mref", "sim.host_s_per_mref"} <= set(
        out["metrics"])
    assert "scan.device_ms_per_mref" not in out["metrics"]   # no TPU here
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("fault", sorted(ROSTER_FAULTS))
def test_roster_run_with_fault_is_not_correct(monkeypatch, capsys,
                                              roster_registry, fault,
                                              tmp_path):
    ROSTER_FAULTS[fault](monkeypatch)
    out = _run(monkeypatch, capsys, _roster_cell(), tmp_path)
    assert out["correct"] is False, out["check"]


def test_window_run_is_correct(monkeypatch, capsys, tmp_path):
    out = _run(monkeypatch, capsys, _window_cell(), tmp_path)
    assert out["correct"] is True, out["check"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_window_run_with_fault_is_not_correct(monkeypatch, capsys, fault,
                                              tmp_path):
    FAULTS[fault](monkeypatch)
    out = _run(monkeypatch, capsys, _window_cell(), tmp_path)
    assert out["correct"] is False, out["check"]


@pytest.mark.parametrize("model", ["qwen", "mamba2"])
def test_a_new_window_cell_runs_from_data_alone(monkeypatch, capsys, model,
                                                tmp_path):
    out = _run(monkeypatch, capsys, _whole_step_cell(model), tmp_path)
    assert out["correct"] is True, out["check"]


@pytest.mark.parametrize("model", ["qwen", "mamba2"])
def test_a_new_window_cell_with_fault_is_not_correct(monkeypatch, capsys,
                                                     model, tmp_path):
    _trace_altered(monkeypatch)
    out = _run(monkeypatch, capsys, _whole_step_cell(model), tmp_path)
    assert out["correct"] is False, out["check"]


def test_ops_the_reference_does_not_generate_are_fed(monkeypatch, capsys,
                                                     tmp_path):
    """With whole-array ops taken out of the reference's walks, their words
    are fed from the program's walk, and the run is still correct."""
    monkeypatch.setattr(reference, "OP_WALKS",
                        {"dense": reference.dense_walk})
    out = _run(monkeypatch, capsys, _whole_step_cell("mamba2"), tmp_path)
    assert out["correct"] is True, out["check"]


def test_no_tpu_means_no_result(capsys):
    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", "table3.r250k", "--seed", "5",
                  "--seconds", "1", "--trace", "0"])
    assert exc.value.code not in (0, None)
    assert "correct" not in capsys.readouterr().out
