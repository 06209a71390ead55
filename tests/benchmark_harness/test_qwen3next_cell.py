"""The Qwen3-Next growing-sessions cell (PR 47): its exact command
rehearsed on the CPU at toy sizes against its own plain reference, after
which NO process of the run is alive; ``benchmarks.run`` of the cell KILLED
while its child is still in set-up, after which nothing of the run is
alive either (what lost PRs 35, 42 and 43: the kind's two guards,
``kinds/serve_hybrid.py``); the cell, the configuration and the mix number
for number; the GDN layers' bytes by hand, held to the engine's own; and
the new readers on a synthetic ``ctx`` — each gives None on a program
without the counters or the kernel, as the parent commit.

What is asserted of BENCHMARK.json's lists is asserted of PR 47's entries
and of what stood before them, never of what a later PR appends."""
import json
import os
import signal
import subprocess
import sys
import time
import uuid

import pytest
from bh_util import (LAST_LINE_KEYS, REPO, declared_pairs, in_order, load_json,
                     read_metric)

from benchmarks import flops_gdn

CELL = "qwen3next-growing-sessions-1chip"
CONFIG = "qwen3next-80b-serve-1chip"
# PR 47's twenty-five; since PR 52 under the readers' own names, each entry
# listing this cell among others
GROW = ["gdn_dev_share", "gdn_decode_roofline",
        "full_attn_dev_share", "full_decode_roofline", "moe_ffn_dev_share",
        "moe_ffn_roofline", "moe_load_max_over_mean",
        "moe_held_assign_share", "state_hit_tok_share",
        "state_rerun_tok_per_turn", "state_pool_live_share",
        "state_snapshot_refused_share", "prefix_hit_tok_share",
        "decode_prog_dev_ms", "prefill_tok_per_dispatch", "decode_slot_occupancy",
        "device_idle_share", "engine_host_share", "dispatch_overlap_share",
        "ttft_turn_ms",
        # the front path, which holds half this cell's rows (PERF.md §7),
        # and the prefill side by a counter every run has: the traced
        # slice (the window's last 4 s) held no prefill dispatch in two
        # traced runs of three, so the chunked kernel's roofline cannot be
        # a declared metric until a ``benchmark`` PR moves the slice
        "front_overhead_ms", "queue_wait_ms", "prefill_span_ms",
        "stream_lag_ms", "prefill_wait_us_per_tok"]
# what no trace is needed for: present (and null) in a rehearsal's line
FROM_COUNTERS = {"moe_load_max_over_mean", "moe_held_assign_share",
                 "state_hit_tok_share", "state_rerun_tok_per_turn",
                 "state_pool_live_share", "state_snapshot_refused_share",
                 "prefix_hit_tok_share", "prefill_tok_per_dispatch",
                 "decode_slot_occupancy", "engine_host_share",
                 "dispatch_overlap_share", "ttft_turn_ms",
                 "front_overhead_ms", "queue_wait_ms", "prefill_span_ms",
                 "stream_lag_ms", "prefill_wait_us_per_tok"}
WORKLOADS_BEFORE = ["docqa-sessions-1chip", "pretrain-4k-1chip",
                    "olmoe-gen-sessions-1chip",
                    "kanana-longdoc-sessions-1chip",
                    "mellum-mixed-queue-1chip"]
MARK = "QWEN3NEXT_CELL_TEST_RUN"


_read = read_metric


def _alive_with(mark: str) -> list:
    """[(pid, command line)] of the processes whose environment carries
    ``MARK=mark``: the run's own ``benchmarks.run``, its child and every
    ``ray_tpu.core.worker`` inherit it."""
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


def _command(mark: str, trace: int, **extra):
    command = load_json(REPO, "BENCHMARK.json")["command"]
    env = dict(os.environ, JAX_PLATFORMS="cpu", **{MARK: mark}, **extra)
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    return ([sys.executable, *command[1:], "--workload", CELL, "--seed",
             str(2 ** 31 + 13), "--seconds", "2", "--trace", str(trace),
             "--rehearse"], env)


def test_cell_rehearses_and_nothing_of_the_run_outlives_it():
    """The driver's command with ``--rehearse --trace 1``; the cell's
    metrics that need no device are in its line, null; later turns resumed
    from snapshots; and once it has returned nothing it started is alive."""
    mark = uuid.uuid4().hex
    argv, env = _command(mark, 1)
    proc = subprocess.run(argv, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=200)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    # at once: the kind's sweep returns only when nothing carries its tag
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
    assert counters["state_snapshot_hits"] > 0
    assert counters["state_hit_tokens"] > counters["state_rerun_tokens"] > 0
    assert 0 < counters["moe_assign_held"] < counters["moe_expert_load_sum"]
    ready = next(json.loads(ln) for ln in proc.stdout.splitlines()
                 if ln.startswith('{"phase": "ready"'))
    assert ready["reference"]["context_prefix_tokens_saved"] > 0
    # the snapshot the state check's ask filed (resumed from the first
    # ask's, prefilled on) is the reference's scan state: float32 against
    # float32 here, so rounding alone, and no entry of it a bf16
    state = ready["reference"]["state"]
    assert state["ok"] and state["resumed_tokens"] > 0
    assert len(state["gap"]) == 3 and max(state["gap"]) < 1e-5
    assert max(state["conv_gap"]) < 1e-5
    assert state["bf16_exact_share"] < 1e-3
    assert "left_running" not in proc.stdout


@pytest.mark.parametrize("sig", [signal.SIGKILL, signal.SIGTERM],
                         ids=["SIGKILL", "SIGTERM"])
def test_a_run_killed_during_set_up_leaves_nothing(sig):
    """``benchmarks.run`` of the cell killed while its child sits in
    set-up (``ray_tpu.init`` has started the workers; the child's set-up
    is held by SERVE_HYBRID_SETUP_DELAY_S): a killed process runs no
    ``finally``, so it is the child's watcher that ends the child, the head
    and the workers. ``/proc`` walked at once and after 5 s."""
    mark = uuid.uuid4().hex
    argv, env = _command(mark, 0, SERVE_HYBRID_SETUP_DELAY_S="90")
    proc = subprocess.Popen(argv, cwd=REPO, env=env, start_new_session=True,
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)
    try:
        deadline = time.monotonic() + 60
        workers = []
        while time.monotonic() < deadline and len(workers) < 2:
            workers = [c for _, c in _alive_with(mark)
                       if "ray_tpu.core" in c]
            time.sleep(0.25)
        started = _alive_with(mark)
        assert any("serve_hybrid_child" in c for _, c in started), started
        assert len(workers) >= 2, started
        proc.send_signal(sig)
        proc.wait(timeout=10)
        at_once = _alive_with(mark)
        deadline = time.monotonic() + 5
        while _alive_with(mark) and time.monotonic() < deadline:
            time.sleep(0.1)
        assert _alive_with(mark) == [], (at_once, _alive_with(mark))
        time.sleep(max(deadline - time.monotonic(), 0))
        assert _alive_with(mark) == []
    finally:
        for pid, _ in _alive_with(mark):
            os.kill(pid, signal.SIGKILL)


def test_the_state_check_fails_a_state_kept_below_float32():
    """``serve_app_hybrid.HybridBenchLLMServer`` in this process at the
    rehearsal's sizes: the check passes the program; with the reference's
    recurrent state kept in bf16 (the spec's ``control``: what a program
    that held its states in bf16 reads against a float32 reference) the
    state's comparison fails the check, in every GDN layer, while the
    share of served tokens — all it had before — still passes."""
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
        held = server.reference_check(
            dict(spec, control={"state_dtype": "bfloat16"},
                 min_share=0.9), context)
        assert not held["ok"] and "state" in held["why"], held
        assert held["share"] >= 0.9
        assert min(held["state"]["gap"]) > 10 * spec["state_gap_limit"]
    finally:
        server._stop = True


def test_the_prefill_side_is_in_every_line_by_a_counter():
    before = {"ns_prefill_device": 0, "prefill_tokens": 0}
    after = {"ns_prefill_device": 15_000_000_000, "prefill_tokens": 300_000}
    ctx = _ctx(stats_before=before, stats_after=after)
    assert _read("prefill_wait_us_per_tok", ctx) == 50.0


def test_sweep_kills_what_carries_the_tag_and_says_what_it_left():
    from benchmarks.kinds import serve_hybrid
    tag = uuid.uuid4().hex
    sleeper = subprocess.Popen(
        [sys.executable, "-c", "import time; time.sleep(60)"],
        env=dict(os.environ, **{serve_hybrid.TAG_ENV: tag}),
        start_new_session=True)
    try:
        assert [p for p, _ in serve_hybrid.tagged(tag)] == [sleeper.pid]
        said = []
        assert serve_hybrid.sweep(tag, said.append) == [] and said == []
        assert sleeper.wait(timeout=5) == -signal.SIGKILL
        assert serve_hybrid.tagged(tag) == []
    finally:
        sleeper.kill()


def test_cell_config_and_mix_are_what_the_issue_names(bench_root):
    def _json(*path):
        return load_json(bench_root, *path)
    bench = _json("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "growing-sessions", 1) and len(cell["why"]) <= 200
    names = [w["name"] for w in bench["workloads"]]
    assert in_order(WORKLOADS_BEFORE + [CELL], names)
    out = next(m for m in bench["end_to_end"] if m["name"] == "out_tok_s")
    assert CELL in out["workloads"]
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_experts", "vocab_size",
                                "num_hidden_layers"]
    cfg = _json(entry["file"])
    assert cfg["source"] == entry["source"] and cfg["kind"] == "serve_hybrid"
    assert set(cfg["reduced"]) == set(entry["reduced"])
    # every width as published
    published = dict(
        hidden_size=2048, head_dim=256, num_attention_heads=16,
        num_key_value_heads=2, linear_num_key_heads=16,
        linear_num_value_heads=32, linear_key_head_dim=128,
        linear_value_head_dim=128, linear_conv_kernel_dim=4,
        moe_intermediate_size=512, shared_expert_intermediate_size=512,
        num_experts_per_tok=10, full_attention_interval=4,
        partial_rotary_factor=0.25, rope_theta=10000000,
        intermediate_size=5120, max_position_embeddings=262144)
    assert {k: cfg[k] for k in published} == published
    # the three cuts, and the published counts beside them
    assert (cfg["num_experts"], cfg["experts_routed"],
            cfg["experts_held"]) == (128, 512, [0, 128])
    assert cfg["vocab_size"] == 151936 // 4 == 37984
    assert cfg["num_hidden_layers"] == 8
    assert "v5e-16" in cfg["deployment"] and cfg["assumed"]
    eng = cfg["engine"]
    assert (eng["max_batch_size"], eng["num_pages"],
            eng["max_pages_per_seq"]) == (64, 65536, 1024)
    mix = _json("benchmarks", "traffic", "growing-sessions.json")
    assert mix["generator"] == "growing_sessions" and mix["sessions"] == 64
    assert mix["first_turn_tokens"] == {
        "dist": "uniform", "min": 2048, "max": 8192, "multiple_of": 16}
    assert (mix["message_tokens"]["min"], mix["message_tokens"]["max"]) == (
        128, 512)
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 192,
                                    "sigma": 0.5, "min": 64, "max": 384}
    assert mix["max_context_tokens"] == 16384 and mix["warmup_s"] <= 45
    assert mix["request_timeout_s"] == 120 and mix["think_time_s"] == 0
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in GROW:
        m = per_layer[name]
        assert CELL in m["workloads"] and m["moves"] == "out_tok_s"


def test_the_arithmetic_of_the_configuration_file():
    cfg = load_json(REPO, "benchmarks", "configs", f"{CONFIG}.json")
    assert flops_gdn.expert_params(cfg) == 3 * 2048 * 512 == 3145728
    assert (flops_gdn.full_layers(cfg), flops_gdn.gdn_layers(cfg)) == (2, 6)
    assert flops_gdn.kv_bytes_per_token(cfg) == 4096
    assert flops_gdn.state_bytes_layer(cfg) == 32 * 128 * 128 * 4 == 2097152
    assert flops_gdn.conv_dim(cfg) == 8192
    assert flops_gdn.state_bytes(cfg) == 6 * (2097152 + 3 * 8192 * 2)
    total = flops_gdn.total_params(cfg)
    assert 3.66e9 < total < 3.68e9          # 7.3 GB of bf16
    # a quarter of a decode step's 640 assignments reach the held experts
    assert flops_gdn.held_assignments(cfg, 64) == 160
    assert 90 < flops_gdn.held_experts_hit(cfg, 64) < 100
    assert flops_gdn.gdn_decode_bytes(cfg, 64) == 64 * 2 * 2097152


def test_the_engine_counts_a_state_as_the_benchmark_does():
    """``flops_gdn.state_bytes`` / ``kv_bytes_per_token`` are the engine's
    own ``state_nbytes`` / ``page_nbytes`` at the rehearsal's sizes."""
    from benchmarks.spec import Cell, resolve
    from ray_tpu.llm.paged_engine import (PagedEngineConfig,
                                          PagedInferenceEngine)
    cfg = Cell(CELL).sizes(True)["config"]
    builder = resolve(cfg["builder"])(cfg)
    eng = PagedInferenceEngine(PagedEngineConfig(
        model=builder.cfg, **cfg["engine"]), builder.init_params(0))
    assert eng.state_nbytes == flops_gdn.state_bytes(cfg, dtype_bytes=4)
    assert eng.page_nbytes == cfg["engine"]["page_size"] \
        * flops_gdn.kv_bytes_per_token(cfg, dtype_bytes=4)
    assert eng.window_page_nbytes == 0


def _ctx(**over):
    cfg = load_json(REPO, "benchmarks", "configs", f"{CONFIG}.json")
    ctx = {"config": cfg, "device": {"kind": "TPU v5 lite"},
           "records": [], "rehearse": False}
    ctx.update(over)
    return ctx


@pytest.mark.parametrize("name", GROW)
def test_a_reader_finds_nothing_on_a_program_without_its_source(name):
    """The parent commit has no such kernel, counter or tag: no raise, no
    number."""
    empty = {"ns_admit": 0}
    trace = {"devices": 1, "window_s": 0.0, "busy_s": 0.0,
             "busy_s_worst": 0.0, "kernels": {}, "families": {}, "ops": [],
             "stats_before": empty, "stats_after": empty}
    assert _read(name, _ctx()) is None
    assert _read(name, _ctx(trace=trace, stats_before=empty,
                            stats_after=empty)) is None


def test_the_gdn_rooflines_by_hand():
    cfg = load_json(REPO, "benchmarks", "configs", f"{CONFIG}.json")
    before = {"decode_live_slots": 0, "decode_dispatches": 0,
              "prefill_dispatches": 0, "prefill_tokens": 0,
              "prefill_rows_live": 0}
    after = {"decode_live_slots": 640, "decode_dispatches": 10,
             "prefill_dispatches": 2, "prefill_tokens": 4096,
             "prefill_rows_live": 32}
    trace = {"devices": 1, "window_s": 1.0, "busy_s": 0.5,
             "busy_s_worst": 0.5, "families": {}, "ops": [],
             "kernels": {"gdn_decode": {"seconds": 0.048, "count": 60},
                         "gdn_prefill": {"seconds": 0.012, "count": 12}},
             "stats_before": before, "stats_after": after}
    ctx = _ctx(trace=trace)
    hbm = 819e9
    # 64 live rows x 2 MiB in and out over the peak rate, against 0.8 ms
    assert _read("gdn_decode_roofline", ctx) == pytest.approx(
        100 * (64 * 2 * 2097152 / hbm) / 0.0008)
    tokens, rows = 2048, 16
    least = (tokens * (8192 * 2 + 4096 * 4) + rows * 2 * 2097152) / hbm
    # the chunked kernel's least time a dispatch (no declared reader: the
    # traced slice may hold no prefill): memory-bound by its own count
    assert flops_gdn.gdn_prefill_bytes(cfg, tokens, rows) == pytest.approx(
        least * hbm)
    assert least > flops_gdn.gdn_token_flops(cfg) * tokens / 197e12
    assert _read("gdn_dev_share", ctx) == pytest.approx(
        100 * (0.048 + 0.012) / 0.5)


def test_the_state_readers_by_hand():
    before = dict.fromkeys((
        "state_hit_tokens", "state_rerun_tokens", "admitted",
        "state_pool_live", "decode_dispatches", "state_snapshots_taken",
        "state_snapshots_refused", "moe_assign_held", "moe_held_load_max",
        "moe_expert_load_sum"), 0)
    after = dict(before, state_hit_tokens=9000, state_rerun_tokens=1000,
                 admitted=2, state_pool_live=800, decode_dispatches=10,
                 state_snapshots_taken=3, state_snapshots_refused=1,
                 moe_assign_held=2560, moe_held_load_max=40,
                 moe_expert_load_sum=10240)
    ctx = _ctx(stats_before=before, stats_after=after)
    assert _read("state_hit_tok_share", ctx) == 90.0
    assert _read("state_rerun_tok_per_turn", ctx) == 500.0
    assert _read("state_pool_live_share", ctx) == 100.0 * 80 / 160
    assert _read("state_snapshot_refused_share", ctx) == 25.0
    assert _read("moe_held_assign_share", ctx) == 25.0
    assert _read("moe_load_max_over_mean", ctx) == 40 / (2560 / 128)
