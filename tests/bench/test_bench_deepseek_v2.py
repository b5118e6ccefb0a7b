"""The DeepSeek-V2 configuration of the benchmark: its file against the
program's published configuration, a tiny decode step of it streamed
whole through the window check, and the reader of the whole-model
capture's metric (``capture.model_s_per_mref``)."""

from __future__ import annotations

import json

import pytest

from bench import manifest, spans
from bench.devtrace import DeviceStats
from bench.run import Context
from test_bench_check import _run, _trace_altered

CONFIG = "deepseek-v2-decode"


def _config() -> dict:
    return json.loads(manifest.config_path(CONFIG).read_text())


def test_file_states_the_published_model_cut_in_depth_and_experts_only():
    """Every field the file sets is the program's published number, but
    for the two cuts, whose published values the file states."""
    from repro import configs

    cfg = _config()
    published = configs.get(cfg["program_config"])
    cut = {"num_hidden_layers": 5, "n_routed_experts": 20}
    assert cfg["published"] == {"num_hidden_layers": 60,
                                "n_routed_experts": 160}
    for field, key in cfg["program_fields"].items():
        if key == "expert_capacity":
            assert cfg[key] == 64     # assumed: the whole batch
        elif key in cut:
            assert cfg[key] == cut[key]
            assert cfg["published"][key] == getattr(published, field)
        elif key == "n_router_experts":
            assert cfg[key] == published.router_experts == 160
        else:
            assert cfg[key] == getattr(published, field), (field, key)


def _tiny_cell() -> manifest.Cell:
    """The configuration and its traffic at CPU widths (every mechanism
    kept: q-LoRA, YaRN, the dense first layer, 4 of 16 group-routed
    experts held), the step streamed whole."""
    cfg = dict(_config(), hidden_size=64, intermediate_size=128,
               moe_intermediate_size=32, num_attention_heads=4,
               num_key_value_heads=4, q_lora_rank=48, kv_lora_rank=32,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               vocab_size=512, num_hidden_layers=3, n_routed_experts=4,
               n_router_experts=16, n_group=4, topk_group=2,
               num_experts_per_tok=3, expert_capacity=8)
    traffic = json.loads(manifest.traffic_path("decode.bs64c4k.latent")
                         .read_text())
    traffic.update(batch=8, cache_len=64, window_refs=1 << 30)
    m = manifest.load_manifest()
    return manifest.Cell("deepseek-v2.whole_step.tiny", 1, cfg, traffic,
                         tuple(m["end_to_end"]), tuple(m["per_layer"]))


def test_a_tiny_deepseek_v2_step_runs_from_data_alone(monkeypatch, capsys,
                                                       tmp_path):
    out = _run(monkeypatch, capsys, _tiny_cell(), tmp_path)
    assert out["correct"] is True, out["check"]
    assert all(n["value"] == 0 for n in out["check"].values())


def test_a_tiny_deepseek_v2_step_with_fault_is_not_correct(monkeypatch,
                                                            capsys, tmp_path):
    _trace_altered(monkeypatch)
    out = _run(monkeypatch, capsys, _tiny_cell(), tmp_path)
    assert out["correct"] is False, out["check"]


# --------------------------------------------------------------------------
# The reader of capture.model_s_per_mref.
# --------------------------------------------------------------------------
METRIC = "capture.model_s_per_mref"
REFS = 2_000_000          # 2 Mref: a reading is seconds over 2


def sp(name, start_us, dur_us, tid=1):
    return spans.Span(name, 1, tid, float(start_us), float(dur_us))


def read(span_list, refs=REFS):
    ctx = Context(refs, spans.nest(span_list),
                  DeviceStats(window_ns=1.0, chips=1))
    return manifest.load_reader(METRIC)(ctx)


def test_reader_is_in_the_manifest_for_the_window_cells():
    m = {x["name"]: x for x in manifest.load_manifest()["per_layer"]}[METRIC]
    assert (m["source"], m["layer"], m["moves"]) == (
        "program_span", "capture", "sim_refs_per_s")
    assert set(m["workloads"]) == {"qwen14b.decode.bs64c4k",
                                   "qwen14b.decode.bs8c32k.ffn",
                                   "dsv2.decode.bs64c4k"}


def test_reader_sums_the_self_time_of_the_model_capture_spans():
    """Trace, jaxpr walk and window placement count, less the op walks
    nested in them; the walks' own spans and lookalikes do not."""
    got = read([
        sp("bench.job", 0, 20_000_000),
        sp("capture.model.trace", 0, 2_000_000),            # 2 s
        sp("capture.model.walk_jaxpr", 2_000_000, 1_000_000),  # 1 s
        sp("capture.model.window", 4_000_000, 3_000_000),   # 3 s ...
        sp("capture.walk", 4_500_000, 2_000_000),            # ... less 2 s
        sp("capture.model.window", 9_000_000, 500_000, tid=2),
        sp("capture.modelx", 12_000_000, 4_000_000),
        sp("capture.walk.emit", 16_000_000, 1_000_000),
    ])
    assert got == pytest.approx((2.0 + 1.0 + 1.0 + 0.5) / 2)


def test_reader_gives_none_where_its_spans_are_absent():
    assert read([]) is None
    assert read([sp("bench.job", 0, 10), sp("capture.walk", 1, 5)]) is None
    assert read([sp("capture.model.trace", 0, 10)], refs=0) is None
