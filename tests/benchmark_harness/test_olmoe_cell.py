"""The OLMoE cell (PR 27): its exact command rehearsed on the CPU at toy
sizes (8 experts, top-2) against its own plain reference; the operations
and bytes of an expert layer counted by hand; and the new readers on a
synthetic ``ctx`` — each gives None on a program without the counters or
the kernel, as every commit before this PR and every dense configuration."""
import json
import os

import pytest
from bh_util import (LAST_LINE_KEYS, declared_pairs, load_json, read_metric,
                     rehearse)

from benchmarks import flops_moe

CELL = "olmoe-gen-sessions-1chip"
OLMOE = {"hidden_size": 2048, "intermediate_size": 1024, "num_experts": 64,
         "num_experts_per_tok": 8, "num_attention_heads": 16,
         "num_key_value_heads": 16, "num_hidden_layers": 10,
         "vocab_size": 50304}


_read = read_metric


def test_cell_rehearses_with_its_metrics_present_and_null():
    line = rehearse(CELL, trace=1)
    assert LAST_LINE_KEYS <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    # what the counters alone give is there; what needs a device trace
    # finds nothing to read on the CPU and is left out
    assert {"moe_live_assign_share", "moe_load_max_over_mean",
            "decode_slot_occupancy", "decode_tok_per_dispatch",
            "engine_host_share", "prefix_hit_tok_share",
            "decode_dead_row_share"} <= set(line["metrics"])
    assert all(m["value"] is None for m in line["metrics"].values())
    # and nothing that another cell's entry alone declares
    assert set(line["metrics"]) <= {n for n, c in declared_pairs()
                                    if c == CELL}


# PR 27's thirteen less ``decode_step_ms`` (retired by PR 52); since PR 52
# under the readers' own names, each entry listing this cell among others
GEN = ["moe_ffn_dev_share", "moe_ffn_roofline", "moe_live_assign_share",
       "moe_load_max_over_mean", "decode_prog_dev_ms",
       "decode_slot_occupancy", "decode_tok_per_dispatch",
       "ragged_attn_dev_share", "ragged_decode_roofline",
       "engine_host_share", "device_idle_share", "prefix_hit_tok_share"]


def test_cell_is_what_the_issue_names(bench_root):
    """On the tree and on a copy with a fifth cell appended: the twelve
    are among the per-layer metrics that list the cell, and what is
    asserted of a metric is asserted of them."""
    bench = load_json(bench_root, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "olmoe7b-serve-1chip", "gen-sessions", 1)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["out_tok_s"]["workloads"]
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    assert len(GEN) == 12 and set(GEN) <= {m["name"] for m in mine}
    assert all(m["moves"] == "out_tok_s" for m in mine if m["name"] in GEN)
    cfg = load_json(bench_root, "benchmarks", "configs",
                    "olmoe7b-serve-1chip.json")
    # every width as published; only the depth is cut
    for key, value in OLMOE.items():
        assert cfg[key] == value, key
    assert cfg["norm_topk_prob"] is False and list(cfg["reduced"]) == [
        "num_hidden_layers"]
    assert cfg["engine"]["max_batch_size"] == 64
    mix = load_json(bench_root, "benchmarks", "traffic", "gen-sessions.json")
    assert mix["sessions"] == cfg["engine"]["max_batch_size"]
    assert mix["warmup_s"] == pytest.approx(
        (mix["sessions"] - 1) * mix["stagger_s"] + 1)
    longest = (mix["document_tokens"]["max"] + mix["question_tokens"]["max"]
               + mix["output_tokens"]["max"])
    assert longest == 1664 <= mix["max_context_tokens"]


def test_flops_moe_counted_by_hand_for_one_layer():
    m = OLMOE
    assert flops_moe.expert_params(m) == 3 * 2048 * 1024 == 6_291_456
    assert flops_moe.layer_expert_params(m) == 402_653_184   # 402.7 M
    assert flops_moe.router_params(m) == 131_072
    # attention 4 x 2048 x 2048 = 16.8 M, QK-norm 2 x 2048, two norms
    assert flops_moe.layer_params(m) == (
        16_777_216 + 4096 + 4096 + 131_072 + 402_653_184) == 419_569_664
    # 10 layers + embedding and head (206 M) + final norm: 4.40 B
    assert flops_moe.total_params(m) == (
        10 * 419_569_664 + 2 * 50304 * 2048 + 2048) == 4_401_743_872
    # 64 decode rows x top-8 = 512 assignments reach all 64 experts
    # (63.98 under uniform routing); one token reaches its 8 (7.56)
    assert flops_moe.expected_experts_hit(m, 64) == pytest.approx(
        64 * (1 - (63 / 64) ** 512)) == pytest.approx(63.98, abs=0.01)
    assert flops_moe.expected_experts_hit(m, 1) == pytest.approx(7.57,
                                                                 abs=0.01)
    # 805 MB of weights and 2 x 512 rows of 2048 bf16 in and out
    assert flops_moe.expert_ffn_bytes(m, 64) == pytest.approx(
        63.98 * 6_291_456 * 2 + 512 * 2 * 2048 * 2, rel=1e-4)
    assert flops_moe.expert_ffn_bytes(m, 64) / 819e9 == pytest.approx(
        0.98e-3, rel=0.01)                                  # the issue's
    assert flops_moe.expert_ffn_flops(m, 64) == 2 * 512 * 6_291_456


def _trace(ops):
    return {"devices": 1, "busy_s": 2.0, "window_s": 2.2,
            "kernels": {"moe_ffn": {"seconds": 0.9, "count": 1200}},
            "ops": ops}


def test_moe_readers_on_a_synthetic_ctx():
    before = {"moe_assign_live": 1000, "moe_assign_run": 2000,
              "moe_expert_load_sum": 2000, "moe_expert_load_max": 50}
    after = {"moe_assign_live": 10_000, "moe_assign_run": 12_000,
             "moe_expert_load_sum": 12_000, "moe_expert_load_max": 250}
    ctx = {"stats_before": before, "stats_after": after,
           "config": dict(OLMOE, engine={"max_batch_size": 64}),
           "device": {"kind": "TPU v5 lite"},
           "trace": _trace([
               ["grouped_swiglu:bf16[1472,1024]", 0.40, 400, "tpu_custom_call"],
               ["grouped_matmul:bf16[1472,2048]", 0.20, 400, "tpu_custom_call"],
               ["grouped_swiglu:bf16[3008,1024]", 0.20, 100, "tpu_custom_call"],
               ["grouped_matmul:bf16[3008,2048]", 0.10, 100, "tpu_custom_call"],
               ["fusion:bf16[64,2048]", 0.30, 4000, ""]])}
    assert _read("moe_live_assign_share", ctx) == pytest.approx(90.0)
    # (250 - 50) x 64 / 10,000: the busiest expert got 1.28 x the mean
    assert _read("moe_load_max_over_mean", ctx) == pytest.approx(1.28)
    assert _read("moe_ffn_dev_share", ctx) == pytest.approx(45.0)
    # decode shape = the calls with the fewest rows: 0.6 s in 400 layer
    # steps = 1.5 ms, where 805 MB + rows need 0.985 ms
    least = flops_moe.expert_ffn_bytes(OLMOE, 64) / 819e9
    assert _read("moe_ffn_roofline", ctx) == pytest.approx(
        100 * least / 1.5e-3)
    assert 60 < _read("moe_ffn_roofline", ctx) < 70
    # the same reader under a configuration whose key for ONE expert's
    # width is ``moe_intermediate_size`` (Mellum's: ``intermediate_size``
    # is a dense MLP its layers do not use): that key decides
    other = dict(ctx, config=dict(ctx["config"], intermediate_size=7168,
                                  moe_intermediate_size=1024))
    assert _read("moe_ffn_roofline", other) == _read("moe_ffn_roofline", ctx)


@pytest.mark.parametrize("name", [
    "moe_live_assign_share", "moe_load_max_over_mean",
    "moe_ffn_dev_share", "moe_ffn_roofline"])
def test_moe_readers_give_none_without_counters_or_kernel(name):
    """The parent commit and every dense configuration: no ``moe_*`` keys
    in ``engine.stats``, no grouped kernel in the trace, no ``moe_ffn``
    group where the pattern file is absent."""
    dense = {"stats_before": {"decode_steps": 1}, "stats_after":
             {"decode_steps": 9}, "config": dict(OLMOE, engine={
                 "max_batch_size": 64}), "device": {"kind": "TPU v5 lite"},
             "trace": {"devices": 1, "busy_s": 2.0, "window_s": 2.2,
                       "kernels": {}, "ops": [
                           ["fusion:bf16[64,2048]", 0.3, 4000, ""]]}}
    assert _read(name, dense) is None
    assert _read(name, dict(dense, trace=None)) is None
    assert _read(name, {"config": dense["config"]}) is None


def test_wide_tokenizer_keeps_one_character_a_token():
    """50,304 ids do not fit between U+4E00 and the surrogates: the ids
    OneCharTokenizer covers map as it maps them, the rest to one character
    each of the supplementary planes, which JSON carries as an escape
    pair and ``json.loads`` joins again (the client counts characters)."""
    from benchmarks.tokenizer import OneCharTokenizer
    from benchmarks.tokenizer_wide import WideTokenizer
    with pytest.raises(ValueError):
        OneCharTokenizer(50304)
    wide, narrow = WideTokenizer(50304), OneCharTokenizer(32768)
    ids = [0, 1, 32767, 35327, 35328, 40000, 50303]
    text = wide.decode(ids)
    assert len(text) == len(ids) and wide.encode(text) == ids
    assert wide.decode(ids[:3]) == narrow.decode(ids[:3])
    assert len(set(wide.decode(range(50304)))) == 50304
    for dumped in (json.dumps({"text": text}),
                   json.dumps({"text": text}, ensure_ascii=False)):
        assert json.loads(dumped.encode().decode())["text"] == text
