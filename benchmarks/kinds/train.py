"""A training cell, from the parent's side: start the driver child, wait
for the trainer's result, and hold it to the plain reference."""
from __future__ import annotations

import math

from ..proc import Child, child_env


def run(cell, a, t_process_start: float, log) -> dict:
    sizes = cell.sizes(a.rehearse)
    cfg, mix = sizes["config"], sizes["traffic"]
    child = Child("benchmarks.kinds.train_child",
                  ["--workload", cell.name, "--seed", str(a.seed),
                   "--seconds", str(a.seconds), "--trace", str(int(a.trace))]
                  + (["--rehearse"] if a.rehearse else []),
                  child_env(a.rehearse, cell.chips), log=log)
    failed = True
    try:
        child.read(120, event="session")
        m = child.read(1150 + a.seconds, event="result")["metrics"]
        failed = False
    finally:
        if failed:
            log(child.worker_log_tails())
        child.stop()
        child.remove_session_dirs()
    tc = cfg["train"]
    rel = lambda x, y: abs(x - y) / max(abs(y), 1e-30)   # noqa: E731
    finite = all(math.isfinite(x) for x in [m["loss0"], *m["losses"]])
    checks = {
        # the first step's loss against the reference's forward loss on
        # the same batch: bf16 weights and activations against float32
        # arithmetic differ in the third digit of a loss near ln(vocab)
        "loss_vs_reference": rel(m["loss0"], m["ref_loss"])
        <= tc["loss_rel_tol"],
        # gradient norm of a slice against jax.grad of the reference
        "grad_vs_reference": rel(m["prog_grad_norm"], m["ref_grad_norm"])
        <= tc["grad_rel_tol"],
        "loss_finite_and_falling": finite and m["losses"][-1] < m["loss0"],
    }
    log({"phase": "train", "steps": m["steps"], "window_s": m["window_s"],
         "loss0": m["loss0"], "ref_loss": m["ref_loss"],
         "loss_last": m["losses"][-1], "prog_grad_norm": m["prog_grad_norm"],
         "ref_grad_norm": m["ref_grad_norm"], "split": m["split"]})
    return {
        "ctx": {"cell": cell, "config": cfg, "traffic": mix, "train": m, "trace": m["trace"],
                "device": m["device"], "seconds": a.seconds,
                "setup_s": m["open_wall"] - t_process_start},
        "attempted": m["steps"], "failed": 0 if finite else m["steps"],
        "checks": checks, "device": m["device"],
    }
