"""The Ling-3.0-flash long-generation cell (PR 50): its exact command
rehearsed on the CPU at toy sizes against its own plain reference, after
which NO process of the run is alive; the cell, the configuration and the
mix number for number, and every published key of the catalog's row where
the file has it; the KDA layers' and the latent pool's bytes by hand, held
to the engine's own; the new readers on a synthetic ``ctx`` — each gives
None on a program without the counters or the kernel, as the parent
commit; and each of the reference's four controls fails the check.

What is asserted of BENCHMARK.json's lists is asserted of PR 50's entries
and of what stood before them, never of what a later PR appends."""
import json
import os
import subprocess
import sys
import uuid

import pytest
from bh_util import (LAST_LINE_KEYS, REPO, declared_pairs, in_order, load_json,
                     read_metric)

from benchmarks import flops_kda

CELL = "ling-longgen-mixed-1chip"
CONFIG = "ling3flash-125b-serve-1chip"
# PR 50's seven, under the readers' own names since PR 52
LGEN = ["kda_dev_share", "kda_decode_roofline", "latent_decode_roofline",
        "moe_ffn_roofline", "moe_group_hit_share", "decode_prog_dev_ms",
        "device_idle_share"]
# what waited for a slot until PR 52 made room: twenty of ISSUE 50's names
# from readers that were there, the chunked kernel's roofline (new), and
# the handle's share of the front overhead (new, every serving cell)
WAITED = ["decode_slot_occupancy", "dispatch_overlap_share",
          "engine_host_share", "front_overhead_ms", "latent_attn_dev_share",
          "full_pool_live_share", "moe_ffn_dev_share",
          "moe_held_assign_share", "moe_load_max_over_mean",
          "prefill_tok_per_dispatch", "prefill_wait_us_per_tok",
          "prefix_hit_tok_share", "queue_wait_ms",
          "ragged_decode_sweep_fill", "state_hit_tok_share",
          "state_pool_live_share", "state_snapshot_refused_share",
          "stream_lag_ms", "ttft_short_ms", "ttft_long_ms",
          "kda_prefill_roofline", "router_wait_ms"]
# what no trace is needed for: present (and null) in a rehearsal's line
FROM_COUNTERS = {"moe_group_hit_share", "decode_slot_occupancy",
                 "dispatch_overlap_share", "engine_host_share",
                 "front_overhead_ms", "full_pool_live_share",
                 "moe_held_assign_share", "moe_load_max_over_mean",
                 "prefill_tok_per_dispatch", "prefill_wait_us_per_tok",
                 "prefix_hit_tok_share", "queue_wait_ms",
                 "ragged_decode_sweep_fill", "state_hit_tok_share",
                 "state_pool_live_share", "state_snapshot_refused_share",
                 "stream_lag_ms", "ttft_short_ms", "ttft_long_ms",
                 "router_wait_ms"}
WORKLOADS_BEFORE = ["docqa-sessions-1chip", "pretrain-4k-1chip",
                    "olmoe-gen-sessions-1chip",
                    "kanana-longdoc-sessions-1chip",
                    "mellum-mixed-queue-1chip",
                    "qwen3next-growing-sessions-1chip"]
MARK = "LING_CELL_TEST_RUN"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


_read = read_metric


def _alive_with(mark: str) -> list:
    needle = f"{MARK}={mark}".encode()
    out = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/environ", "rb") as f:
                if needle not in f.read().split(b"\0"):
                    continue
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                out.append((int(pid), f.read().replace(b"\0", b" ").decode()))
        except OSError:
            continue            # gone, or another user's
    return out


def test_cell_rehearses_and_nothing_of_the_run_outlives_it():
    """The driver's command with ``--rehearse --trace 1`` and a seed over
    2**31; the cell's metrics that need no device are in its line, null; the reference check's repeated document resumed a snapshot; the
    state check held the three KDA layers' snapshot to the reference's
    scan; and once it has returned nothing it started is alive."""
    mark = uuid.uuid4().hex
    command = load_json(REPO, "BENCHMARK.json")["command"]
    env = dict(os.environ, JAX_PLATFORMS="cpu", **{MARK: mark})
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    proc = subprocess.run(
        [sys.executable, *command[1:], "--workload", CELL, "--seed",
         str(2 ** 31 + 17), "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    assert _alive_with(mark) == []
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert LAST_LINE_KEYS <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    assert FROM_COUNTERS <= set(line["metrics"])
    assert all(m["value"] is None for m in line["metrics"].values())
    # and nothing that another cell's entry alone declares
    assert set(line["metrics"]) <= {n for n, c in declared_pairs()
                                    if c == CELL}
    window = next(json.loads(ln) for ln in proc.stdout.splitlines()
                  if ln.startswith('{"phase": "window"'))
    counters = window["counters_in_window"]
    # (whether a document's second ask still finds its snapshot under the
    # short class's churn is the cell's to show: PERF.md section 7)
    assert counters["state_snapshots_taken"] > 0
    assert counters["state_rerun_tokens"] > 0
    assert 0 < counters["moe_assign_held"] < counters["moe_expert_load_sum"]
    assert 0 < counters["moe_group_hits"] \
        < counters["moe_expert_load_sum"] // 2
    assert counters["full_pool_live_pages"] > 0
    assert window["cache_bytes_per_token"] == 128 * 4    # one latent layer
    ready = next(json.loads(ln) for ln in proc.stdout.splitlines()
                 if ln.startswith('{"phase": "ready"'))
    assert ready["reference"]["context_prefix_tokens_saved"] > 0
    state = ready["reference"]["state"]
    assert state["ok"] and state["resumed_tokens"] > 0
    assert len(state["gap"]) == 3 and max(state["gap"]) < 1e-5
    assert max(state["conv_gap"]) < 1e-5
    assert state["bf16_exact_share"] < 1e-3
    assert "left_running" not in proc.stdout


def test_cell_config_and_mix_are_what_the_issue_names(bench_root):
    def _json(*path):
        return load_json(bench_root, *path)
    bench = _json("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "longgen-mixed", 1) and len(cell["why"]) <= 200
    names = [w["name"] for w in bench["workloads"]]
    assert in_order(WORKLOADS_BEFORE + [CELL], names)
    out = next(m for m in bench["end_to_end"] if m["name"] == "out_tok_s")
    assert CELL in out["workloads"]
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_experts", "vocab_size",
                                "num_hidden_layers"]
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    cfg = _json(entry["file"])
    assert cfg["source"] == entry["source"] and cfg["kind"] == "serve_hybrid"
    assert set(cfg["reduced"]) == set(entry["reduced"])
    # every width as published
    published = dict(
        hidden_size=2560, head_dim=128, num_attention_heads=32,
        num_key_value_heads=32, kv_lora_rank=512, qk_head_dim=192,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        q_lora_rank=None, intermediate_size=6144, moe_intermediate_size=768,
        moe_shared_expert_intermediate_size=768, num_shared_experts=1,
        num_experts_per_tok=8, n_group=8, topk_group=4,
        routed_scaling_factor=2.5, layer_group_size=6,
        first_k_dense_replace=2, short_conv_kernel_size=4,
        kda_lower_bound=-5, rope_theta=6000000,
        max_position_embeddings=262144)
    assert {k: cfg[k] for k in published} == published
    # the three cuts, and the published counts beside them
    assert (cfg["num_experts"], cfg["experts_routed"],
            cfg["experts_held"]) == (64, 512, [0, 64])
    assert cfg["vocab_size"] == 157184 // 8 == 19648
    assert cfg["num_hidden_layers"] == 7 and cfg["dense_layers_kept"] == 1
    assert "v5e-32" in cfg["deployment"]
    assert {"decay", "gates", "use_qk_norm", "swiglu_limits",
            "a_log_dt_bias", "router", "state", "mtp"} <= set(cfg["assumed"])
    eng = cfg["engine"]
    assert (eng["page_size"], eng["num_pages"], eng["num_state_snapshots"],
            eng["max_batch_size"], eng["max_pages_per_seq"]) == (
        16, 98304, 128, 128, 1024)
    assert eng["enable_prefix_caching"] and not eng["kv_spill"] \
        and eng["spec_tokens"] == 0
    assert set(cfg["reference_check"]) >= {
        "min_share", "logit_margin", "state_gap_limit",
        "state_gap_limit_gross", "bf16_exact_limit"}
    mix = _json("benchmarks", "traffic", "longgen-mixed.json")
    assert mix["generator"] == "closed_sessions_mixed"
    assert mix["short"] == {
        "sessions": 112,
        "prompt_tokens": {"dist": "lognormal", "median": 1024, "sigma": 0.5,
                          "min": 512, "max": 2048},
        "output_tokens": {"dist": "lognormal", "median": 2048, "sigma": 0.5,
                          "min": 1024, "max": 4096}}
    assert mix["long"] == {
        "sessions": 16,
        "document_tokens": {"dist": "uniform", "min": 8192, "max": 12288,
                            "multiple_of": 16},
        "questions_per_session": 2,
        "question_tokens": {"dist": "uniform", "min": 64, "max": 256},
        "output_tokens": {"dist": "lognormal", "median": 1024, "sigma": 0.5,
                          "min": 512, "max": 2048}}
    assert mix["short"]["sessions"] + mix["long"]["sessions"] \
        == eng["max_batch_size"]
    assert mix["max_context_tokens"] == 16384 >= 12288 + 256 + 2048
    assert mix["request_timeout_s"] == 300 and mix["think_time_s"] == 0
    # every session has started when the window opens
    assert mix["long"]["sessions"] * mix["stagger_s"] + 1 \
        == mix["warmup_s"] <= 45
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    assert len(WAITED) == 22 and not set(WAITED) & set(LGEN)
    for name in LGEN + WAITED:
        m = per_layer[name]
        assert CELL in m["workloads"] and m["moves"] == "out_tok_s"


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_file_holds_the_catalog_rows_numbers():
    """Every key of the catalog row's ``config`` is in the file under the
    same key with the same value, the three ``reduced`` keys apart; nested
    groups (the two lists of limits) are copied whole."""
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Ling-3.0-flash")
    cfg = load_json(REPO, "benchmarks", "configs", f"{CONFIG}.json")
    assert cfg["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if cfg.get(k, "-") != v}
    assert differ == {"num_experts", "vocab_size", "num_hidden_layers"}
    assert row["config"]["num_experts"] == cfg["experts_routed"] == 512
    assert row["config"]["vocab_size"] == 8 * cfg["vocab_size"]


def test_the_arithmetic_of_the_configuration_file():
    cfg = load_json(REPO, "benchmarks", "configs", f"{CONFIG}.json")
    assert flops_kda.expert_params(cfg) == 3 * 2560 * 768 == 5898240
    assert (flops_kda.mla_layers(cfg), flops_kda.kda_layers(cfg),
            flops_kda.dense_layers(cfg), flops_kda.expert_layers(cfg)) == (
        1, 6, 1, 6)
    assert flops_kda.latent_bytes_per_token(cfg) == 640 * 2 == 1280
    assert flops_kda.state_bytes_layer(cfg) == 32 * 128 * 128 * 4 == 2097152
    assert flops_kda.conv_dim(cfg) == 12288
    assert flops_kda.state_bytes(cfg) == 6 * (2097152 + 3 * 12288 * 2) \
        == 13025280
    total = flops_kda.total_params(cfg)
    assert 2.86e9 < total < 2.88e9          # 5.73 GB of bf16
    eng = cfg["engine"]
    pools = (129 + 129) * flops_kda.state_bytes(cfg) \
        + eng["num_pages"] * eng["page_size"] * 1280
    assert 11.0e9 < 2 * total + pools < 11.2e9
    # the mix cannot run the latent pool dry
    assert 112 * (2048 + 4096) + 16 * (12288 + 256 + 2048) \
        < (eng["num_pages"] - 1) * eng["page_size"]
    # an eighth of a decode step's 1,024 assignments reach the held 64
    assert flops_kda.held_assignments(cfg, 128) == 128
    assert flops_kda.group_hit_share(cfg) == 0.5
    assert flops_kda.kda_decode_bytes(cfg, 128) == 128 * 2 * 2097152
    # the chunked kernel is memory-bound by its own counts at the chip's
    # ridge of 240 FLOPs a byte: its roofline is bytes over the HBM rate
    tokens, rows = 2048, 16
    assert flops_kda.kda_prefill_bytes(cfg, tokens, rows) == (
        tokens * (12288 * 2 + 2 * 4096 * 4) + rows * 2 * 2097152)
    assert flops_kda.kda_prefill_flops(cfg, tokens) \
        / flops_kda.kda_prefill_bytes(cfg, tokens, rows) < 240


def test_the_engine_counts_the_two_kinds_as_the_benchmark_does():
    """``flops_kda.state_bytes`` / ``latent_bytes_per_token`` are the
    engine's own ``state_nbytes`` / ``page_nbytes`` at the rehearsal's
    sizes (float32: the lanes are the same, the bytes twice)."""
    from benchmarks.spec import Cell, resolve
    from ray_tpu.llm.paged_engine import (PagedEngineConfig,
                                          PagedInferenceEngine)
    cfg = Cell(CELL).sizes(True)["config"]
    builder = resolve(cfg["builder"])(cfg)
    eng = PagedInferenceEngine(PagedEngineConfig(
        model=builder.cfg, **cfg["engine"]), builder.init_params(0))
    assert eng.state_nbytes == flops_kda.state_bytes(cfg, dtype_bytes=4)
    assert eng.page_nbytes == cfg["engine"]["page_size"] \
        * flops_kda.latent_bytes_per_token(cfg, dtype_bytes=4)
    assert eng.window_page_nbytes == 0


def _ctx(**over):
    cfg = load_json(REPO, "benchmarks", "configs", f"{CONFIG}.json")
    ctx = {"config": cfg, "device": {"kind": "TPU v5 lite"},
           "records": [], "rehearse": False}
    ctx.update(over)
    return ctx


def test_a_reader_finds_nothing_on_a_program_without_its_source():
    """The parent commit has no such kernel or counter: no raise, no
    number, from any of the cell's readers (one case: the file's cases are
    kept few so that it sorts to the end of the suite's queue)."""
    empty = {"ns_admit": 0}
    trace = {"devices": 1, "window_s": 0.0, "busy_s": 0.0,
             "busy_s_worst": 0.0, "kernels": {}, "families": {}, "ops": [],
             "stats_before": empty, "stats_after": empty}
    for name in LGEN + WAITED:
        assert _read(name, _ctx()) is None, name
        assert _read(name, _ctx(trace=trace, stats_before=empty,
                                stats_after=empty)) is None, name


def test_the_readers_by_hand():
    before = {"decode_live_slots": 0, "decode_dispatches": 0,
              "decode_steps": 0, "moe_held_hit_decode": 0,
              "decode_live_pages": 0, "moe_group_hits": 0,
              "moe_expert_load_sum": 0}
    after = {"decode_live_slots": 1200, "decode_dispatches": 10,
             "decode_steps": 40, "moe_held_hit_decode": 40 * 6 * 50,
             "decode_live_pages": 10 * 50000, "moe_group_hits": 3000,
             "moe_expert_load_sum": 48000}
    ops = [["grouped_swiglu.3:bf16[3072,768]", 0.024, 240],
           ["grouped_matmul.3:bf16[3072,2560]", 0.012, 240],
           ["grouped_swiglu.9:bf16[36736,768]", 0.2, 12],
           ["grouped_matmul.9:bf16[36736,2560]", 0.1, 12],
           ["ragged_paged_attention_latent.2:bf16[128,1,32,512]", 0.08, 40]]
    trace = {"devices": 1, "window_s": 1.0, "busy_s": 0.8,
             "busy_s_worst": 0.8, "ops": ops,
             "families": {"decode": {"total_s": 0.6, "steps": 40,
                                     "median_s": 0.06}},
             "kernels": {"kda_decode": {"seconds": 0.24, "count": 240},
                         "kda_prefill": {"seconds": 0.04, "count": 12}},
             "stats_before": before, "stats_after": after}
    ctx = _ctx(trace=trace, stats_before=before, stats_after=after)
    hbm = 819e9
    # 120 live rows x 2 MiB in and out over the peak rate, against 1 ms
    assert _read("kda_decode_roofline", ctx) == pytest.approx(
        100 * (120 * 2 * 2097152 / hbm) / 0.001)
    assert _read("kda_dev_share", ctx) == pytest.approx(
        100 * (0.24 + 0.04) / 0.8)
    # 50 distinct held experts a layer a step (the counter over 6 EXPERT
    # layers, not 7), an eighth of 128 x 8 assignments: the experts'
    # weights and the rows over the HBM rate, against 0.15 ms a layer-step
    least = 2 * (50 * 5898240 + 128 * 2 * 2560) / hbm
    assert _read("moe_ffn_roofline", ctx) == pytest.approx(
        100 * least / (0.036 / 240))
    # 50,000 live pages of 16 tokens x 1,152 B, against 2 ms a call
    assert _read("latent_decode_roofline", ctx) == pytest.approx(
        100 * (50000 * 16 * 1152 / hbm) / 0.002)
    # 3,000 of 48,000 / 8 = 6,000 tokens x layers
    assert _read("moe_group_hit_share", ctx) == 50.0
    assert _read("decode_prog_dev_ms", ctx) == pytest.approx(15.0)
    assert _read("device_idle_share", ctx) == pytest.approx(20.0)


def test_what_waited_for_a_slot_by_hand():
    """The readers PR 52 declared for this cell, on this configuration's
    file: the folded ones take the held experts' counters (64 of 512) and
    the expert layers alone; the chunked kernel's roofline is new."""
    cfg = load_json(REPO, "benchmarks", "configs", f"{CONFIG}.json")
    zero = dict.fromkeys((
        "prefill_tokens", "prefill_dispatches", "prefill_rows_live",
        "decode_dispatches", "decode_live_slots", "moe_held_load_max",
        "moe_assign_held", "moe_expert_load_max", "moe_expert_load_sum",
        "full_pool_live_pages", "state_pool_live", "state_hit_tokens",
        "state_rerun_tokens", "ns_prefill_device"), 0)
    # a slice of 10 prefill dispatches of 16 live rows and 2,000 live
    # tokens each; the window's counters beside it
    after = dict(zero, prefill_tokens=20_000, prefill_dispatches=10,
                 prefill_rows_live=160, decode_dispatches=100,
                 decode_live_slots=100 * 120, moe_held_load_max=300,
                 moe_assign_held=6_400, moe_expert_load_max=9_999,
                 moe_expert_load_sum=51_200,
                 full_pool_live_pages=100 * 49_151, state_pool_live=100 * 96,
                 state_hit_tokens=3_000, state_rerun_tokens=9_000,
                 ns_prefill_device=1_000_000_000)
    trace = {"devices": 1, "window_s": 1.0, "busy_s": 0.8,
             "busy_s_worst": 0.8, "ops": [], "families": {},
             "kernels": {"kda_prefill": {"seconds": 0.03, "count": 60},
                         "kda_decode": {"seconds": 0.24, "count": 240},
                         "latent_attention": {"seconds": 0.08, "count": 50}},
             "stats_before": zero, "stats_after": after}
    ctx = _ctx(trace=trace, stats_before=zero, stats_after=after)
    # a call: 2,000 tokens x (12,288 channels of q, k, v in bf16 + the
    # decay read and the output written, 4,096 float32 each) + 16 rows'
    # states in and out = 181.8 MB, 0.222 ms at the peak rate; its 18.9
    # GFLOP need 0.096 ms: memory-bound. Against 0.5 ms a call
    by = 2000 * (12288 * 2 + 2 * 4096 * 4) + 16 * 2 * 2097152
    assert flops_kda.kda_prefill_bytes(cfg, 2000, 16) == by
    assert flops_kda.kda_prefill_flops(cfg, 2000) / 197e12 < by / 819e9
    assert _read("kda_prefill_roofline", ctx) == pytest.approx(
        100 * (by / 819e9) / 0.0005)
    assert 0 < _read("kda_prefill_roofline", ctx) < 100
    # a slice without a prefill dispatch, or without the kernel: nothing
    none = dict(after, prefill_tokens=0, prefill_dispatches=0,
                prefill_rows_live=0)
    assert _read("kda_prefill_roofline",
                 _ctx(trace=dict(trace, stats_after=none))) is None
    assert _read("kda_prefill_roofline", _ctx(trace=dict(trace, kernels={
        "kda_prefill": {"seconds": 0.0, "count": 0}}))) is None
    assert _read("kda_prefill_roofline", dict(ctx, rehearse=True)) is None
    # the busiest HELD expert's 300 over the mean held expert's 6,400 / 64
    assert _read("moe_load_max_over_mean", ctx) == pytest.approx(3.0)
    assert _read("moe_held_assign_share", ctx) == pytest.approx(12.5)
    assert _read("latent_attn_dev_share", ctx) == pytest.approx(10.0)
    assert _read("full_pool_live_share", ctx) == pytest.approx(
        100 * 49_151 / 98_303)
    assert _read("state_pool_live_share", ctx) == pytest.approx(75.0)
    assert _read("state_hit_tok_share", ctx) == pytest.approx(25.0)
    assert _read("prefill_tok_per_dispatch", ctx) == pytest.approx(2000.0)
    assert _read("prefill_wait_us_per_tok", ctx) == pytest.approx(50.0)
    assert _read("decode_slot_occupancy", ctx) == pytest.approx(
        100 * 120 / 128)


def test_each_control_fails_the_reference_check():
    """``serve_app_hybrid.HybridBenchLLMServer`` in this process at the
    rehearsal's sizes: the check passes the program, and fails it against
    the reference under each of its four controls — the state kept in
    bf16 and the decay averaged over a head's channels by the state's own
    comparison (the first KDA layer's), the group selection skipped and
    3 mantissa bits by the share of served tokens."""
    from benchmarks.serve_app_hybrid import HybridBenchLLMServer
    from benchmarks.spec import Cell, resolve
    from benchmarks.tokenizer_wide import WideTokenizer
    from ray_tpu.llm.paged_engine import PagedEngineConfig
    from ray_tpu.llm.serving import LLMConfig
    sizes = Cell(CELL).sizes(True)
    cfg, spec = sizes["config"], sizes["config"]["reference_check"]
    context = int(sizes["traffic"]["max_context_tokens"])
    builder = resolve(cfg["builder"])(cfg)
    server = HybridBenchLLMServer(
        LLMConfig(model_id="bench", warmup=False, engine=PagedEngineConfig(
            model=builder.cfg, tokenizer=WideTokenizer(cfg["vocab_size"]),
            **cfg["engine"])),
        {"seed": 5, "chips": 1, "rehearse": True, "builder": cfg["builder"],
         "reference": cfg["reference"],
         "model": {k: v for k, v in cfg.items() if not isinstance(v, dict)}})
    try:
        sound = server.reference_check(spec, context)
        assert sound["ok"] and sound["state"]["ok"], sound
        for control in ({"state_dtype": "bfloat16"}, {"decay": "head_mean"}):
            held = server.reference_check(dict(spec, control=control),
                                          context)
            assert not held["ok"], (control, held)
            assert held["state"]["gap"][0] > 10 * spec["state_gap_limit"]
        for control in ({"groups": False}, {"round_to": "float8_e4m3fn"}):
            held = server.reference_check(dict(spec, control=control),
                                          context)
            assert not held["ok"] and held["share"] < spec["min_share"], (
                control, held)
    finally:
        server._stop = True
