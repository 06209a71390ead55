"""The Mellum2 mixed-queue cell (PR 40): its exact command rehearsed on the
CPU at toy sizes against its own plain reference, after which NO process
of the run is alive; the cell, the configuration and the mix number for
number; the window layers' bytes, pairs and pages by hand; and the new
readers on a synthetic ``ctx`` — each gives None on a program without the
counters or the kernel, as the parent commit.

What is asserted of BENCHMARK.json's lists is asserted of PR 40's entries
and of what stood before them, never of what a later PR appends after
them: the structural test takes ``bench_root`` (``conftest.py``) and runs on
the tree and on a copy with a fifth cell appended."""
import json
import os
import subprocess
import sys
import time
import uuid

import pytest
from bh_util import (LAST_LINE_KEYS, REPO, declared_pairs, load_json,
                     read_metric, stands_before)

from benchmarks import flops_window

CELL = "mellum-mixed-queue-1chip"
CONFIG = "mellum2-12b-serve-1chip"
# PR 40's twenty; since PR 52 under the readers' own names, each entry
# listing this cell among others
MIXQ = ["window_attn_dev_share", "full_attn_dev_share",
        "window_decode_roofline", "full_decode_roofline",
        "window_prefill_roofline", "window_pages_returned_share",
        "window_pool_live_share", "full_pool_live_share",
        "prefix_hit_tok_share", "prefix_tail_miss_share", "ttft_short_ms",
        "ttft_long_ms", "moe_ffn_dev_share", "moe_ffn_roofline",
        "moe_load_max_over_mean", "decode_prog_dev_ms",
        "prefill_prog_dev_ms", "decode_slot_occupancy", "device_idle_share",
        "engine_host_share"]
# what no trace is needed for: present (and null) in a rehearsal's line
FROM_COUNTERS = {"window_pages_returned_share", "window_pool_live_share",
                 "full_pool_live_share", "prefix_hit_tok_share",
                 "prefix_tail_miss_share", "ttft_short_ms", "ttft_long_ms",
                 "moe_load_max_over_mean", "decode_slot_occupancy",
                 "engine_host_share"}
# what stood in the lists when PR 40 appended the cell to them
WORKLOADS_BEFORE = ["docqa-sessions-1chip", "pretrain-4k-1chip",
                    "olmoe-gen-sessions-1chip",
                    "kanana-longdoc-sessions-1chip"]
OUT_TOK_S_BEFORE = ["docqa-sessions-1chip", "olmoe-gen-sessions-1chip",
                    "kanana-longdoc-sessions-1chip"]
TAG = "MELLUM_CELL_TEST_RUN"


_read = read_metric


def _alive_with(tag: str) -> list:
    """[(pid, command line)] of the processes whose environment carries
    ``TAG=tag``: the run's own ``benchmarks.run``, its ``*_child`` and every
    ``ray_tpu.core.worker`` (controller, proxy, replica) inherit it."""
    needle = f"{TAG}={tag}".encode()
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
    """The driver's command with ``--rehearse --trace 1``; the cell's
    metrics that need no device are in its line, null; and once it has
    returned no ``ray_tpu.core.worker`` and no ``*_child`` it started is
    alive (what refused PR 35: a later run could be served by one)."""
    tag = uuid.uuid4().hex
    command = load_json(REPO, "BENCHMARK.json")["command"]
    env = dict(os.environ, JAX_PLATFORMS="cpu", **{TAG: tag})
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    proc = subprocess.run(
        [sys.executable, *command[1:], "--workload", CELL, "--seed",
         str(2 ** 31 + 11), "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert LAST_LINE_KEYS <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    assert FROM_COUNTERS <= set(line["metrics"])
    assert all(m["value"] is None for m in line["metrics"].values())
    # and nothing that another cell's entry alone declares
    assert set(line["metrics"]) <= {n for n, c in declared_pairs()
                                    if c == CELL}
    # the window line's counters: both classes were served, pages went
    # back, and the second ask of a document hit through the window tail
    window = next(json.loads(ln) for ln in proc.stdout.splitlines()
                  if ln.startswith('{"phase": "window"'))
    counters = window["counters_in_window"]
    assert counters["window_pages_returned"] > 0
    assert counters["prefix_tokens_saved"] > 0
    ready = next(json.loads(ln) for ln in proc.stdout.splitlines()
                 if ln.startswith('{"phase": "ready"'))
    assert ready["reference"]["context_prefix_tokens_saved"] > 0
    deadline = time.monotonic() + 10.0
    while _alive_with(tag) and time.monotonic() < deadline:
        time.sleep(0.25)
    assert _alive_with(tag) == []


def test_cell_config_and_mix_are_what_the_issue_names(bench_root):
    def _json(*path):
        return load_json(bench_root, *path)
    bench = _json("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "mixed-queue", 1) and len(cell["why"]) <= 200
    # appended, nothing moved: what stood before it still does, in order
    assert stands_before([w["name"] for w in bench["workloads"]], CELL,
                         WORKLOADS_BEFORE)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert len(entry["why"]) <= 200
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert stands_before(e2e["out_tok_s"]["workloads"], CELL,
                         OUT_TOK_S_BEFORE)
    assert "workloads" not in e2e["setup_s"]
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    assert len(MIXQ) == 20 and set(MIXQ) <= {m["name"] for m in mine}
    own = [m for m in mine if m["name"] in MIXQ]
    assert all(m["moves"] == "out_tok_s" for m in own)
    layers = {m["layer"] for m in bench["per_layer"]
              if CELL not in m["workloads"]}
    assert {m["layer"] for m in own} <= layers   # no layer under a new name
    assert all(m["unit"] == "%" and m["source"] == "device_trace"
               for m in own if m["name"].endswith("_roofline"))
    for m in own:       # a reader a metric, found by name
        assert os.path.exists(os.path.join(
            bench_root, "benchmarks", "layer_metrics", m["name"] + ".py"))

    cfg = _json("benchmarks", "configs", f"{CONFIG}.json")
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/"
        "main/config.json")
    # every published key as the catalog's row has it; only the depth cut
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 7168,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "model_type": "mellum", "moe_intermediate_size": 896,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 64, "num_experts_per_tok": 8,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "sliding_window": 1024, "tie_word_embeddings": False,
        "vocab_size": 98304, "use_sliding_window": True}
    for key, value in published.items():
        assert cfg[key] == value, key
    period = ["sliding_attention"] * 3 + ["full_attention"]
    assert cfg["layer_types"] == period * 7
    assert cfg["mlp_layer_types"] == ["sparse"] * 28
    assert cfg["rope_parameters"] == {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
    assert cfg["num_hidden_layers"] == 12 and list(cfg["reduced"]) == [
        "num_hidden_layers"]
    assert cfg["kind"] == "serve_routed" and cfg["mesh"] is None
    assert cfg["rope_parameters_file"] == entry["file"]
    assert set(cfg["assumed"]) >= {"qk_norm", "mtp_head", "layer_types",
                                   "weights"}
    assert "v5litepod-4" in cfg["deployment"]
    # the check's limit lies between its two readings (PERF.md §6)
    assert cfg["reference_check"] == {
        "samples": 4, "prompt_tokens": [64, 512], "new_tokens": 16,
        "logit_margin": 0.1, "context_suffix_tokens": 64, "min_share": 0.96}
    eng = cfg["engine"]
    assert {k: eng[k] for k in eng if k != "num_window_pages"} == {
        "page_size": 16, "num_pages": 25600, "max_batch_size": 32,
        "max_pages_per_seq": 1024, "enable_prefix_caching": True,
        "kv_spill": False, "spec_tokens": 0}
    # 32 live rings of 97 pages and two dispatches in flight (the
    # engine's floor), and room for tails of the documents in flight
    assert eng["num_window_pages"] >= 32 * 97 + 65 + 1024
    # the pools' bytes, by hand: 2,048 B a token a layer
    assert 25600 * 16 * 3 * 2048 == 2_516_582_400
    assert eng["num_window_pages"] * 16 * 9 * 2048 < 2.1e9
    assert set(cfg["rehearsal"]) == {"model", "engine", "reference_check"}

    mix = _json("benchmarks", "traffic", "mixed-queue.json")
    assert mix["generator"] == "closed_sessions_mixed"
    assert mix["long"] == {
        "sessions": 24,
        "document_tokens": {"dist": "uniform", "min": 8192, "max": 15360,
                            "multiple_of": 16},
        "questions_per_session": 3,
        "question_tokens": {"dist": "uniform", "min": 64, "max": 256},
        "output_tokens": {"dist": "lognormal", "median": 256, "sigma": 0.5,
                          "min": 128, "max": 512}}
    assert mix["short"] == {
        "sessions": 8,
        "prompt_tokens": {"dist": "lognormal", "median": 512, "sigma": 0.5,
                          "min": 256, "max": 1024},
        "output_tokens": {"dist": "lognormal", "median": 64, "sigma": 0.5,
                          "min": 16, "max": 192}}
    assert mix["long"]["sessions"] + mix["short"]["sessions"] == eng[
        "max_batch_size"]
    assert (mix["think_time_s"], mix["request_timeout_s"],
            mix["max_context_tokens"]) == (0.0, 120.0, 16384)
    assert mix["warmup_s"] <= 45.0
    assert mix["warmup_s"] == pytest.approx(23 * mix["stagger_s"] + 1)
    longest = 15360 + 256 + 512
    assert longest == 16128 and -(-longest // 16) == 1008
    assert 24 * 1008 == 24192 <= eng["num_pages"] - 1


def test_generator_sends_two_classes_in_one_queue():
    """``closed_sessions_mixed`` against a client that answers at once:
    long sessions ask each document ``questions_per_session`` times in
    turn, the document a shared prefix; short sessions never repeat a
    prompt; every record carries its class; the same seed sends the same
    requests."""
    import asyncio

    import numpy as np

    from benchmarks.client import Window
    from benchmarks.generators import closed_sessions_mixed as gen
    mix = load_json(REPO, "benchmarks", "traffic", "mixed-queue.json")
    traffic = {**mix, **mix["rehearsal"], "stagger_s": 0.0, "warmup_s": 0.0}

    class Client:
        def __init__(self):
            self.sent = []

        def body(self, prompt, max_tokens):
            return list(prompt), int(max_tokens)

        async def send(self, body, due, want, prompt_tokens, **tags):
            assert len(body[0]) == prompt_tokens and body[1] == want
            self.sent.append((tuple(body[0]), want, tags))
            await asyncio.sleep(0.002)

    def drive(seed):
        client = Client()
        window = Window(time.perf_counter(), 0.25)
        asyncio.run(gen.run(traffic, np.random.default_rng([seed, 1]), 512,
                            client, window))
        return client.sent
    sent = drive(2 ** 31 + 5)
    by_cls = {c: [s for s in sent if s[2]["cls"] == c]
              for c in ("long", "short")}
    assert by_cls["long"] and by_cls["short"]
    assert len(by_cls["long"]) + len(by_cls["short"]) == len(sent)
    lo, hi = (traffic["long"]["document_tokens"][k] for k in ("min", "max"))
    docs: dict = {}
    for prompt, want, tags in by_cls["long"]:
        docs.setdefault(tags["doc"], []).append((tags["question"], prompt))
        assert 4 <= want <= 12
    for asks in docs.values():
        assert [q for q, _ in asks] == list(range(len(asks))) and len(
            asks) <= traffic["long"]["questions_per_session"]
        shared = os.path.commonprefix([p for _, p in asks])
        assert len(asks) < 2 or lo <= len(shared) <= hi + 16
    shorts = [p for p, _, _ in by_cls["short"]]
    assert len(set(shorts)) == len(shorts)
    assert all(12 <= len(p) <= 40 for p in shorts)
    # per session the order is the seed's alone (sessions interleave by
    # the clock): the first request of each is the same in a second run
    again = drive(2 ** 31 + 5)
    first = lambda sent: {t["session"]: p for p, _, t in reversed(sent)}  # noqa: E731
    assert first(again) == first(sent)


def test_window_counts_by_hand():
    """One sliding and one full layer at the published widths (4 KV heads
    of 128 in bf16, 32 query heads, window 1,024, pages of 16)."""
    m = load_json(REPO, "benchmarks", "configs", f"{CONFIG}.json")
    assert (flops_window.layers_of(m, flops_window.SLIDING),
            flops_window.layers_of(m, flops_window.FULL)) == (9, 3)
    # keys and values: 2 x 4 x 128 x 2 B
    assert flops_window.kv_bytes_per_token_layer(m) == 2048
    assert flops_window.held_bytes_per_token(m, flops_window.FULL) == 6144
    assert flops_window.held_bytes_per_token(m, flops_window.SLIDING) == 18432
    # QK^T and PV, 2 FLOPs each, 32 heads of 128
    assert flops_window.attn_pair_flops(m) == 4 * 32 * 128 == 16384
    # a 128-token chunk at position 5,000: every query of a sliding layer
    # scores 1,024 keys; of a full layer 5,001 .. 5,128
    assert flops_window.pairs(5000, 128, 1024) == 128 * 1024
    assert flops_window.pairs(5000, 128) == 128 * 5000 + 128 * 129 // 2
    # a first chunk: query q scores q + 1 keys in both
    assert flops_window.pairs(0, 128, 1024) == flops_window.pairs(
        0, 128) == 8256
    # the chunk that crosses the window's edge: queries 1,000 .. 1,022
    # score 1,001 .. 1,023 keys, the other 105 score 1,024
    assert flops_window.pairs(1000, 128, 1024) == (
        sum(range(1001, 1024)) + 105 * 1024)
    # a decode step at position 5,000: keys 3,977 .. 5,000 lie in pages
    # 248 .. 312 (65 pages) of a sliding layer, 0 .. 312 of a full one
    assert flops_window.live_pages(5000, 16, 1024) == 65
    assert flops_window.live_pages(5000, 16) == 313
    assert flops_window.live_pages(10, 16, 1024) == 1
    # what the engine counts (``decode_live_wpages``, at every decode's
    # launch) is what these functions count
    from ray_tpu.llm.kv_cache import WindowPages
    lengths = {0: 5000, 1: 10, 2: 1023, 3: 1024}
    assert WindowPages.live_pages(lengths, range(4), 16, 1024) == sum(
        flops_window.live_pages(n, 16, 1024) for n in lengths.values())


def _ctx(**over):
    cfg = load_json(REPO, "benchmarks", "configs", f"{CONFIG}.json")
    before = {"decode_dispatches": 0, "decode_live_wpages": 0,
              "decode_live_pages": 0, "prefill_rows_live": 0,
              "prefill_rows_padded": 0, "prefill_tokens": 0,
              "prefill_ctx_wpages": 0, "prefill_attn_wpairs": 0,
              "window_pages_claimed": 0, "window_pages_returned": 0,
              "window_pool_live_pages": 0, "full_pool_live_pages": 0,
              "prefix_tokens_saved": 0, "prefix_tail_tokens_lost": 0}
    after = {"decode_dispatches": 100, "decode_live_wpages": 100 * 32 * 65,
             "decode_live_pages": 100 * 32 * 600, "prefill_rows_live": 40,
             "prefill_rows_padded": 40, "prefill_tokens": 40 * 128,
             "prefill_ctx_wpages": 40 * 72,
             "prefill_attn_wpairs": 40 * 128 * 1024,
             "window_pages_claimed": 1000, "window_pages_returned": 900,
             "window_pool_live_pages": 100 * 2303,
             "full_pool_live_pages": 100 * 19199,
             "prefix_tokens_saved": 9000, "prefix_tail_tokens_lost": 1000}
    ops = [
        # name:shape, seconds, count
        ["ragged_paged_attention_window.1:bf16[32,1,32,128]", 0.09, 900],
        ["ragged_paged_attention.2:bf16[32,1,32,128]", 0.3, 300],
        ["ragged_paged_attention_window.3:bf16[1,128,32,128]", 0.018, 360],
        ["ragged_paged_attention.4:bf16[1,128,32,128]", 0.024, 120]]
    trace = {"devices": 1, "busy_s": 4.0, "window_s": 4.2,
             "busy_s_worst": 4.0, "ops": ops, "families": {},
             "kernels": {"window_attention": {"seconds": 0.108},
                         "ragged_attention": {"seconds": 0.432}},
             "stats_before": before, "stats_after": after}
    ctx = {"config": cfg, "device": {"kind": "TPU v5 lite"}, "trace": trace,
           "stats_before": before, "stats_after": after, "records": [],
           "rehearse": False}
    ctx.update(over)
    return ctx


def test_readers_on_a_synthetic_ctx():
    ctx = _ctx()
    assert _read("window_attn_dev_share", ctx) == pytest.approx(2.7)
    assert _read("full_attn_dev_share", ctx) == pytest.approx(8.1)
    hbm = 819e9
    # decode, a sliding layer: 32 x 65 pages x 16 x 2,048 B = 68 MB, 83 us
    # at the peak rate, over 100 us a call
    assert _read("window_decode_roofline", ctx) == pytest.approx(
        100 * (32 * 65 * 16 * 2048 / hbm) / (0.09 / 900))
    assert _read("full_decode_roofline", ctx) == pytest.approx(
        100 * (32 * 600 * 16 * 2048 / hbm) / (0.3 / 300))
    # prefill, a sliding layer, a live row: 128 x 1,024 pairs x 16,384 FLOP
    # (compute-bound: 2.1 GFLOP against 72 pages' 2.4 MB + q and o 2.1 MB)
    fl = 128 * 1024 * 16384 / 197e12
    by = (72 * 16 * 2048 + 2 * 128 * 32 * 128 * 2) / hbm
    assert fl > by
    assert _read("window_prefill_roofline", ctx) == pytest.approx(
        100 * fl / (0.018 / 360))
    assert _read("window_pages_returned_share", ctx) == pytest.approx(90.0)
    pages = ctx["config"]["engine"]
    assert _read("window_pool_live_share", ctx) == pytest.approx(
        100 * 2303 / (pages["num_window_pages"] - 1))
    assert _read("full_pool_live_share", ctx) == pytest.approx(
        100 * 19199 / 25599)
    assert _read("prefix_tail_miss_share", ctx) == pytest.approx(10.0)
    # no share of a roofline is over 100 at these (made-up) times
    for name in ("window_decode_roofline", "full_decode_roofline",
                 "window_prefill_roofline"):
        assert 0 < _read(name, ctx) <= 100

    class Rec:
        def __init__(self, cls, ms, ok=True):
            self.tags, self.ttft_ms, self.ok = {"cls": cls}, ms, ok
            self.prompt_tokens = 1000
    recs = [Rec("short", 40.0), Rec("short", 60.0), Rec("short", 500.0),
            Rec("long", 900.0), Rec("long", 1100.0),
            Rec("long", float("inf"), ok=False)]
    assert _read("ttft_short_ms", _ctx(records=recs)) == 60.0
    assert _read("ttft_long_ms", _ctx(records=recs)) == 1000.0
    assert _read("prefix_hit_tok_share", _ctx(records=recs)) == (
        pytest.approx(100 * 9000 / 6000))


@pytest.mark.parametrize("name", MIXQ)
def test_every_reader_gives_none_without_its_inputs(name):
    """A program without the counters, the kernel or a trace — the parent
    commit, a CPU rehearsal, an untraced run — gives None, never an
    error."""
    cfg = load_json(REPO, "benchmarks", "configs", f"{CONFIG}.json")
    bare = {"config": cfg, "device": {"kind": "TPU v5 lite"}, "records": [],
            "rehearse": False}
    assert _read(name, bare) is None
    assert _read(name, dict(bare, trace=None, stats_before={},
                            stats_after={})) is None
    # a trace of a program that has neither kernel nor counters
    parent = dict(bare, stats_before={"decode_dispatches": 0},
                  stats_after={"decode_dispatches": 5},
                  trace={"devices": 1, "busy_s": 1.0, "window_s": 1.0,
                         "busy_s_worst": 1.0, "ops": [], "families": {},
                         "kernels": {}, "stats_before": {},
                         "stats_after": {}})
    # (the device's idle share needs the trace alone: the parent has one)
    assert name == "device_idle_share" or _read(name, parent) is None
