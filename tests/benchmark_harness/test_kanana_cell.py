"""The kanana long-document cell (PR 31): its exact command rehearsed on
the CPU at toy sizes against its own plain reference; the cell, the
configuration and the mix number for number; the latent cache's bytes,
the attention FLOPs a pair and the parameter counts by hand; and the new
readers on a synthetic ``ctx`` — each gives None on a program without the
counters or the kernel, as the parent commit.

What is asserted of BENCHMARK.json's lists is asserted of PR 31's entries
and of what stood before them, never of what a later PR appends after
them: the structural test takes ``bench_root`` (``conftest.py``) and runs on
the tree and on a copy with a fifth cell appended."""
import json
import os

import pytest
from bh_util import (LAST_LINE_KEYS, REPO, declared_pairs, load_json,
                     read_metric, rehearse, stands_before)

from benchmarks import flops_mla

CELL = "kanana-longdoc-sessions-1chip"
CONFIG = "kanana2-30b-serve-1chip"
# PR 31's sixteen less ``decode_step_ms`` (retired by PR 52); since PR 52
# under the readers' own names, each entry listing this cell among others
LDOC = ["latent_attn_dev_share", "latent_decode_roofline",
        "latent_prefill_roofline", "moe_ffn_dev_share",
        "moe_load_max_over_mean", "decode_prog_dev_ms", "prefill_prog_dev_ms",
        "decode_slot_occupancy", "prefill_row_fill",
        "prefix_hit_tok_share", "engine_host_share", "device_idle_share",
        "queue_wait_ms", "ttft_cold_ms", "ttft_warm_ms"]
# what no trace is needed for: present (and null) in a rehearsal's line
FROM_COUNTERS = {"moe_load_max_over_mean",
                 "decode_slot_occupancy", "prefill_row_fill",
                 "prefix_hit_tok_share", "engine_host_share",
                 "queue_wait_ms", "ttft_cold_ms", "ttft_warm_ms"}


# what stood in the lists when PR 31 appended the cell to them
WORKLOADS_BEFORE = ["docqa-sessions-1chip", "pretrain-4k-1chip",
                    "olmoe-gen-sessions-1chip"]
OUT_TOK_S_BEFORE = ["docqa-sessions-1chip", "olmoe-gen-sessions-1chip"]
# declared by PR 33, after the sixteen (its reader and counters are PR 32's)
LATER = ["prefill_masked_step_share"]


def _json(*path):
    return load_json(REPO, *path)


_read = read_metric


def test_cell_rehearses_with_its_metrics_present_and_null():
    line = rehearse(CELL, trace=1)
    assert LAST_LINE_KEYS <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    # what the counters and the client give is there; what needs a device
    # trace finds nothing to read on the CPU and is left out
    assert FROM_COUNTERS <= set(line["metrics"])
    assert all(m["value"] is None for m in line["metrics"].values())
    # and nothing that another cell's entry alone declares
    assert set(line["metrics"]) <= {n for n, c in declared_pairs()
                                    if c == CELL}


def test_cell_config_and_mix_are_what_the_issue_names(bench_root):
    def _json(*path):
        return load_json(bench_root, *path)
    bench = _json("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "longdoc-sessions", 1) and len(cell["why"]) <= 200
    # appended, nothing moved: what stood before it still does, in order
    assert stands_before([w["name"] for w in bench["workloads"]], CELL,
                         WORKLOADS_BEFORE)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert stands_before(e2e["out_tok_s"]["workloads"], CELL,
                         OUT_TOK_S_BEFORE)
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    assert len(LDOC) == 15
    assert set(LDOC + LATER) <= {m["name"] for m in mine}
    own = [m for m in mine if m["name"] in LDOC + LATER]
    assert all(m["moves"] == "out_tok_s" for m in own)
    assert "decode_step_ms" not in {m["name"] for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]
              if CELL not in m["workloads"]}
    assert {m["layer"] for m in own} <= layers   # no layer under a new name
    masked = next(m for m in own if m["name"] == LATER[0])
    assert (masked["layer"], masked["source"], masked["unit"],
            masked["better"]) == ("kernels", "program_counter", "%", "lower")

    cfg = _json("benchmarks", "configs", f"{CONFIG}.json")
    assert entry["source"] == cfg["source"]
    # every published key as the catalog's row has it; only the depth cut
    published = {
        "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "kv_lora_rank": 512, "max_position_embeddings": 32768,
        "model_type": "deepseek_v3", "moe_intermediate_size": 768,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
        "n_shared_experts": 2, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 1000000, "routed_scaling_factor": 2.448,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
        "vocab_size": 128256}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == 8 and list(cfg["reduced"]) == [
        "num_hidden_layers"]
    assert cfg["kind"] == "serve_routed" and cfg["mesh"] is None
    # the check's limit lies between its two readings (PERF.md §6)
    assert cfg["reference_check"] == {
        "samples": 4, "prompt_tokens": [64, 512], "new_tokens": 64,
        "logit_margin": 0.1, "context_suffix_tokens": 64, "min_share": 0.56}
    assert cfg["builder"] == "benchmarks.models.deepseek_v3:Builder"
    assert cfg["engine"] == {
        "page_size": 16, "num_pages": 25600, "max_batch_size": 32,
        "max_pages_per_seq": 1024, "enable_prefix_caching": True,
        "kv_spill": False, "spec_tokens": 0}     # the rest at its defaults
    assert {"weights", "cache"} <= set(cfg["assumed"]) and cfg["deployment"]

    mix = _json("benchmarks", "traffic", "longdoc-sessions.json")
    assert mix["generator"] == "closed_sessions" and mix["sessions"] == 24
    assert mix["document_tokens"] == {"dist": "uniform", "min": 8192,
                                      "max": 16128, "multiple_of": 16}
    assert mix["questions_per_session"] == 4 and mix["think_time_s"] == 0
    assert mix["question_tokens"] == {"dist": "uniform", "min": 64,
                                      "max": 128}
    assert mix["output_tokens"] == {"dist": "const", "value": 48}
    assert mix["request_timeout_s"] == 120
    assert mix["warmup_s"] == pytest.approx(
        (mix["sessions"] - 1) * mix["stagger_s"] + 1)
    longest = (mix["document_tokens"]["max"] + mix["question_tokens"]["max"]
               + mix["output_tokens"]["value"])
    assert longest == 16304 <= mix["max_context_tokens"] == 16384
    pages = -(-longest // cfg["engine"]["page_size"])
    assert pages == 1019 <= cfg["engine"]["max_pages_per_seq"]
    assert mix["sessions"] * pages == 24456 < cfg["engine"]["num_pages"]


def test_flops_mla_counted_by_hand_for_one_layer():
    m = _json("benchmarks", "configs", f"{CONFIG}.json")
    # c (512) and the one rope key (64): 576 values = 1,152 B of bf16,
    # stored in a row of 640 lanes = 1,280 B
    assert flops_mla.latent_dim(m) == 576
    assert flops_mla.latent_bytes_per_token_layer(m) == 1152
    assert flops_mla.pool_bytes_per_token_layer(m) == 1280
    # 25,600 pages of 16 tokens x 8 layers: the 4.19 GB pool
    assert 25600 * 16 * 8 * flops_mla.pool_bytes_per_token_layer(m) == \
        4_194_304_000
    # a pair: 32 heads x (a 576-wide score + a 512-wide value) x 2
    assert flops_mla.attn_pair_flops(m) == 2 * 32 * (576 + 512) == 69_632
    # Wq 12.58 M, Wkva 1.18 M (+ 512 of norm), Wkvb 4.19 M, Wo 8.39 M
    assert flops_mla.attention_params(m) == (
        2048 * 32 * 192 + 2048 * 576 + 512 + 512 * 32 * 256
        + 32 * 128 * 2048) == 26_345_984
    assert flops_mla.expert_params(m) == 3 * 2048 * 768 == 4_718_592
    assert flops_mla.shared_expert_params(m) == 9_437_184
    assert flops_mla.router_params(m) == 2048 * 128 + 128
    assert flops_mla.dense_mlp_params(m) == 3 * 2048 * 6144 == 37_748_736
    # an expert layer 640.0 M, the dense layer 64.1 M
    assert flops_mla.layer_params(m, dense=False) == (
        26_345_984 + 128 * 4_718_592 + 9_437_184 + 262_272 + 4096
    ) == 640_029_312
    assert flops_mla.layer_params(m, dense=True) == 64_098_816
    # 1 + 7 layers, embedding and head 525.3 M: 5.070 B = 10.14 GB
    assert flops_mla.total_params(m) == (
        64_098_816 + 7 * 640_029_312 + 2 * 128256 * 2048 + 2048
    ) == 5_069_642_624
    assert flops_mla.total_params(dict(m, num_hidden_layers=48)) == \
        pytest.approx(30.67e9, rel=1e-3)


def _ctx(ops, in_slice=None, **stats):
    """``stats``: the counters over the window; ``in_slice``: over the
    traced slice (the snapshots ``trace_stop`` returns beside the
    reduction), left out of the trace when None."""
    zero = dict.fromkeys(stats, 0)
    trace = {"devices": 1, "busy_s": 2.0, "window_s": 2.5,
             "busy_s_worst": 2.0, "ops": ops, "kernels": {
                 "latent_attention": {"seconds": 0.8, "count": 900},
                 "moe_ffn": {"seconds": 0.6, "count": 1400}}}
    if in_slice is not None:
        trace.update(stats_before=dict.fromkeys(in_slice, 0),
                     stats_after=in_slice)
    return {"stats_before": zero, "stats_after": stats,
            "config": _json("benchmarks", "configs", f"{CONFIG}.json"),
            "device": {"kind": "TPU v5 lite"}, "trace": trace}


LATENT = "ragged_paged_attention_latent"


def test_latent_readers_on_a_synthetic_ctx():
    ops = [[f"{LATENT}:bf16[32,1,32,512]", 0.40, 400, "tpu_custom_call"],
           [f"{LATENT}:bf16[4,128,32,512]", 0.30, 50, "tpu_custom_call"],
           [f"{LATENT}:bf16[2,128,32,512]", 0.06, 20, "tpu_custom_call"],
           ["grouped_swiglu:bf16[1024,768]", 0.4, 700, "tpu_custom_call"],
           ["fusion:bf16[32,6144]", 0.2, 4000, ""]]
    in_slice = dict(decode_live_pages=1_536_000, decode_dispatches=100,
                    prefill_rows_live=200, prefill_rows_padded=220,
                    prefill_tokens=200 * 128, prefill_ctx_pages=200 * 768,
                    prefill_attn_pairs=200 * 128 * 12224)
    # the window's counters tell of other documents than the slice's: a
    # fifth more pages a decode dispatch, a fifth more pairs a prefill row.
    # The rooflines take the slice's, the readers of counters alone these
    window = dict(in_slice, decode_live_pages=12 * 1_843_200,
                  decode_dispatches=1200, prefill_rows_live=2400,
                  prefill_rows_padded=2640, prefill_tokens=2400 * 128,
                  prefill_ctx_pages=2400 * 922,
                  prefill_attn_pairs=2400 * 128 * 14669,
                  moe_expert_load_max=300, moe_expert_load_sum=19_200)
    ctx = _ctx(ops, in_slice, **window)
    assert _read("latent_attn_dev_share", ctx) == pytest.approx(40.0)
    assert _read("moe_ffn_dev_share", ctx) == pytest.approx(30.0)
    # 300 x 128 / 19,200: the busiest expert got twice the mean
    assert _read("moe_load_max_over_mean", ctx) == pytest.approx(2.0)
    # decode: 15,360 pages a dispatch = 245,760 live tokens. Bytes
    # 245,760 x 1,152 / 819e9 = 0.3457 ms; operations 245,760 x 69,632 /
    # 197e12 = 0.0869 ms: memory-bound; a call took 1.0 ms
    assert _read("latent_decode_roofline", ctx) == pytest.approx(
        100 * (245_760 * 1152 / 819e9) / 1.0e-3) == pytest.approx(34.57,
                                                                  abs=0.01)
    # prefill: a live row scores 128 x 12,224 pairs: 108.95 GFLOP =
    # 0.5531 ms at peak (its bytes need 0.022 ms: compute-bound). The
    # calls ran 4 x 50 + 2 x 20 = 240 rows in 0.36 s = 1.5 ms a row,
    # x 220 / 200 for the padding the live rows pay for
    least = 128 * 12224 * 69_632 / 197e12
    assert _read("latent_prefill_roofline", ctx) == pytest.approx(
        100 * least / (0.36 / 240 * 1.1)) == pytest.approx(33.52, abs=0.01)
    assert _read("latent_prefill_roofline", ctx) < 100
    # a trace without the slice's snapshots (reduced by the parent's
    # ``trace_stop``): nothing to read, never the window's in their place
    for name in ("latent_decode_roofline", "latent_prefill_roofline"):
        assert _read(name, _ctx(ops, None, **window)) is None
        assert _read(name, _ctx(ops, {}, **window)) is None


@pytest.mark.parametrize("name", [
    "latent_attn_dev_share", "latent_decode_roofline",
    "latent_prefill_roofline", "moe_ffn_dev_share",
    "moe_load_max_over_mean"])
def test_latent_readers_give_none_without_counters_or_kernel(name):
    """The parent commit (no latent kernel, no ``latent_attention`` group
    without the pattern file, and it cannot run the configuration at all)
    and a dense program (no ``moe_*`` counters)."""
    bare = _ctx([["ragged_paged_attention:bf16[32,1,32,128]", 0.3, 400,
                  "tpu_custom_call"], ["fusion:bf16[32,6144]", 0.2, 40, ""]],
                decode_steps=9)
    bare["trace"]["kernels"] = {}
    assert _read(name, bare) is None
    assert _read(name, dict(bare, trace=None)) is None
    assert _read(name, {"config": bare["config"]}) is None


def test_the_folded_readers_tell_this_configuration_by_its_file():
    """``moe_load_max_over_mean`` serves four configurations: this one's
    routed experts are ``n_routed_experts`` (the two shared experts are not
    routed and not counted), and none of them is held apart."""
    cfg = _json("benchmarks", "configs", f"{CONFIG}.json")
    assert "num_experts" not in cfg and "experts_routed" not in cfg
    stats = dict(moe_expert_load_max=300, moe_expert_load_sum=19_200,
                 moe_held_load_max=1, moe_assign_held=1)
    ctx = {"stats_before": dict.fromkeys(stats, 0), "stats_after": stats,
           "config": cfg}
    assert _read("moe_load_max_over_mean", ctx) == pytest.approx(
        300 * cfg["n_routed_experts"] / 19_200) == pytest.approx(2.0)


def test_routed_check_holds_the_share_within_the_margin_not_every_token():
    """``serve_app_routed.RoutedBenchLLMServer.reference_check`` on a stub
    server: three of four served tokens are the reference's argmax and one
    is far from it — a swapped expert — so ``BenchLLMServer``'s limit on
    the worst token fails and the share (0.75) decides."""
    import numpy as np

    from benchmarks import serve_app, serve_app_routed

    class Ref:
        def __init__(self, model):
            pass

        def hidden(self, params, tokens):
            import jax.numpy as jnp
            return jnp.eye(8)[tokens % 8]          # [S, 8]

        def head(self, params, x):
            return x * 2.0                         # argmax = token % 8

    class Engine:
        stats = {"prefix_tokens_saved": 0}
        params = {}

    class Stub(serve_app_routed.RoutedBenchLLMServer):
        def __init__(self):
            self._bench = {"seed": 1, "model": {"vocab_size": 8},
                           "reference": f"{__name__}:REF"}
            self.engine = Engine()
            self.engine_cfg = type("C", (), {"enable_prefix_caching": True})

        def completions(self, request):
            # the reference's argmax at every position is the token fed
            # there: serve it, but for the last position
            prompt = request["prompt"]
            self.engine.stats["prefix_tokens_saved"] += 8
            toks = [prompt[-1]] * request["max_tokens"]
            toks[-1] = (toks[-1] + 3) % 8
            return {"choices": [{"token_ids": toks}]}

    globals()["REF"] = Ref
    spec = {"samples": 2, "prompt_tokens": [4, 8], "new_tokens": 4,
            "logit_margin": 0.1, "context_suffix_tokens": 2,
            "min_share": 0.7}
    out = Stub().reference_check(spec, 64)
    assert out["tokens"] == 16 and out["within_margin"] == 12
    assert out["share"] == 0.75 and out["ok"] is True
    assert out["worst_logit_gap"] == {"short": 2.0, "context": 2.0}
    assert Stub().reference_check(dict(spec, min_share=0.8), 64)["ok"] \
        is False
    assert isinstance(Stub(), serve_app.BenchLLMServer)
    assert np.isclose(out["share_within_margin"]["short"], 0.75)
