"""LoRATrainer: per-tenant fine-tuning on the Train substrate.

The train leg of the train -> publish -> serve loop (PAPERS.md:
"Fine-Tuning and Serving Gemma ... on Google Cloud TPU" — per-tenant
adapters fine-tuned on the training substrate, then served hot). Base
weights stay FROZEN; only the adapter factors A/B train (A ~ N(0, s),
B = 0, the standard LoRA init, so step 0 is exactly the base model).
The forward differentiates THROUGH llm/lora.py's merge — the identical
W + (alpha/r)·A@B math the merged serving engine runs, so a trained
adapter's serving outputs are the model the trainer optimized.

Two execution modes:

- ``scaling_config=None`` (default): the loop runs in-process — the
  CI-scale path and what notebooks want;
- with a ScalingConfig, the loop runs under train.DataParallelTrainer
  (gang scheduling, failure handling, result bus) with
  session.report()/Checkpoint per checkpoint_every steps and
  SIGKILL-safe resume via session.get_checkpoint().

Both modes checkpoint {step, adapter, opt} through train.Checkpoint
and both resume from the latest one. ``publish()`` lands the trained
adapter in the AdapterRegistry, where serving replicas' managers pick
it up live (no engine restart — the hot-swap path).
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Optional

import numpy as np

from .registry import AdapterRegistry


@dataclasses.dataclass
class LoRATrainConfig:
    model: Any                       # llama.LlamaConfig
    rank: int = 4
    alpha: float = 8.0
    targets: tuple = ("wq", "wv")
    learning_rate: float = 5e-2
    steps: int = 40
    batch_size: int = 4
    seq_len: int = 32
    checkpoint_every: int = 10
    seed: int = 0


def _init_adapter(tcfg: LoRATrainConfig):
    """Trainable factors: A random, B zero (delta starts at exactly 0)."""
    import jax
    from ...models import llama as _llama

    out = {}
    rng = jax.random.PRNGKey(tcfg.seed)
    cfg = tcfg.model
    for t in tcfg.targets:
        if t == "lm_head":
            din, dout, lead = cfg.dim, cfg.vocab_size, ()
        elif t == "wq":
            din, dout, lead = cfg.dim, cfg.n_heads * cfg.head_dim, \
                (cfg.n_layers,)
        elif t in ("wk", "wv"):
            din, dout, lead = cfg.dim, cfg.n_kv_heads * cfg.head_dim, \
                (cfg.n_layers,)
        elif t == "wo":
            din, dout, lead = cfg.n_heads * cfg.head_dim, cfg.dim, \
                (cfg.n_layers,)
        else:
            raise ValueError(f"unknown LoRA target {t!r}")
        rng, ka = jax.random.split(rng)
        out[f"{t}.A"] = (jax.random.normal(
            ka, lead + (din, tcfg.rank)) * 0.02).astype(np.float32)
        out[f"{t}.B"] = np.zeros(lead + (tcfg.rank, dout), np.float32)
    del _llama  # shape math above needs only the config
    return out


def _default_data(tcfg: LoRATrainConfig) -> Callable:
    """Plain LM objective on random token streams (callers pass a real
    data_fn; this keeps the trainer runnable out of the box)."""
    def data_fn(step: int):
        rng = np.random.RandomState(tcfg.seed * 100003 + step)
        toks = rng.randint(1, tcfg.model.vocab_size,
                           (tcfg.batch_size, tcfg.seq_len + 1))
        return toks[:, :-1].astype(np.int32), toks[:, 1:].astype(np.int32)
    return data_fn


def _run_loop(tcfg: LoRATrainConfig, base_params, data_fn,
              state: Optional[dict], report_cb) -> dict:
    """The loop both modes share. ``state`` resumes {step, adapter,
    opt_leaves}; ``report_cb(step, loss, state_dict)`` fires every
    checkpoint_every steps and at the end. Returns the final state."""
    import jax
    import optax

    from .. import lora
    from ...models import llama

    opt = optax.adam(tcfg.learning_rate)
    if state is None:
        adapter = _init_adapter(tcfg)
        opt_state = opt.init(adapter)
        start = 0
    else:
        adapter = {k: np.asarray(v, np.float32)
                   for k, v in state["adapter"].items()}
        opt_state = jax.tree.unflatten(
            jax.tree.structure(opt.init(adapter)),
            [np.asarray(leaf) for leaf in state["opt_leaves"]])
        start = int(state["step"])
    scalars = {"rank": np.int32(tcfg.rank),
               "alpha": np.float32(tcfg.alpha)}
    mc = tcfg.model

    @jax.jit
    def step_fn(ad, opt_state, tokens, targets):
        def loss_fn(a):
            merged = lora.merge(base_params, {**a, **scalars})
            logits = llama.apply(merged, tokens, mc)
            return llama.cross_entropy_loss(logits, targets)
        loss, grads = jax.value_and_grad(loss_fn)(ad)
        updates, opt_state = opt.update(grads, opt_state, ad)
        return optax.apply_updates(ad, updates), opt_state, loss

    loss = float("nan")
    for i in range(start, tcfg.steps):
        tokens, targets = data_fn(i)
        adapter, opt_state, loss = step_fn(
            adapter, opt_state, np.asarray(tokens, np.int32),
            np.asarray(targets, np.int32))
        done = i + 1 >= tcfg.steps
        if done or (i + 1) % tcfg.checkpoint_every == 0:
            state = {"step": np.int32(i + 1),
                     "adapter": jax.device_get(adapter),
                     "opt_leaves": jax.device_get(
                         jax.tree.leaves(opt_state))}
            report_cb(i + 1, float(loss), state)
    if state is None:      # steps == 0 degenerate case
        state = {"step": np.int32(start),
                 "adapter": jax.device_get(adapter),
                 "opt_leaves": jax.device_get(jax.tree.leaves(opt_state))}
        report_cb(start, float(loss), state)
    return state


def _as_published(tcfg: LoRATrainConfig, adapter_arrays: dict) -> dict:
    """Trained factors -> the llm/lora.py adapter format (what the
    registry stores, lora.merge merges, and the slot table loads)."""
    return {"rank": np.int32(tcfg.rank), "alpha": np.float32(tcfg.alpha),
            **{k: np.asarray(v, np.float32)
               for k, v in adapter_arrays.items()}}


class LoRATrainer:
    """Fine-tune one adapter; checkpoint/resume; publish to a registry."""

    def __init__(self, tcfg: LoRATrainConfig, adapter_id: str,
                 base_params: Optional[dict] = None,
                 data_fn: Optional[Callable] = None,
                 storage_path: Optional[str] = None,
                 registry: Optional[AdapterRegistry] = None,
                 scaling_config=None, run_config=None):
        self.tcfg = tcfg
        self.adapter_id = adapter_id
        self._base_params = base_params
        self.data_fn = data_fn or _default_data(tcfg)
        self.storage_path = storage_path
        self.registry = registry or AdapterRegistry()
        self.scaling_config = scaling_config
        self.run_config = run_config
        self.adapter: Optional[dict] = None   # set by fit()
        self.last_loss: Optional[float] = None

    def _base(self):
        if self._base_params is None:
            import jax

            from ...models import llama
            self._base_params = llama.init(
                jax.random.PRNGKey(self.tcfg.seed), self.tcfg.model)
        return self._base_params

    # -- local (in-process) mode -----------------------------------------

    def _fit_local(self) -> dict:
        from ...train.checkpoint import Checkpoint, CheckpointManager
        manager = None
        state = None
        if self.storage_path:
            manager = CheckpointManager(
                os.path.join(self.storage_path, self.adapter_id,
                             "checkpoints"), num_to_keep=2)
            manager.scan_existing()
            if manager.latest is not None:
                try:
                    state = manager.latest.load_state()
                except Exception:
                    state = None   # truncated checkpoint: start over

        losses = []

        def report(step, loss, st):
            losses.append(loss)
            if manager is not None:
                manager.register(
                    Checkpoint.from_state(st, metadata={"step": step}),
                    {"step": step, "loss": loss})

        state = _run_loop(self.tcfg, self._base(), self.data_fn, state,
                          report)
        self.last_loss = losses[-1] if losses else None
        return state

    # -- Train-substrate mode --------------------------------------------

    def _fit_substrate(self) -> dict:
        import cloudpickle

        from ... import train as train_mod
        tcfg, data_fn = self.tcfg, self.data_fn
        base_blob = cloudpickle.dumps(self._base())

        def train_fn():
            import cloudpickle as _cp

            from ray_tpu import train as ts
            base = _cp.loads(base_blob)
            restored = ts.get_checkpoint()
            state = restored.load_state() if restored is not None else None

            def report(step, loss, st):
                ck = ts.Checkpoint.from_state(st, metadata={"step": step})
                ts.report({"step": step, "loss": loss}, checkpoint=ck)

            _run_loop(tcfg, base, data_fn, state, report)

        trainer = train_mod.DataParallelTrainer(
            train_fn, scaling_config=self.scaling_config,
            run_config=self.run_config)
        result = trainer.fit()
        if result.checkpoint is None:
            raise RuntimeError("LoRA training finished without a "
                               "checkpoint (steps < checkpoint_every?)")
        self.last_loss = (result.metrics or {}).get("loss")
        return result.checkpoint.load_state()

    # -- public surface ---------------------------------------------------

    def fit(self) -> dict:
        """Train (or resume) and return the adapter in llm/lora.py
        format."""
        state = (self._fit_local() if self.scaling_config is None
                 else self._fit_substrate())
        self.adapter = _as_published(self.tcfg, state["adapter"])
        return self.adapter

    def publish(self) -> int:
        """Land the trained adapter in the registry; serving replicas'
        managers observe the new version within their refresh TTL and
        hot-swap without an engine restart. Returns the version."""
        if self.adapter is None:
            raise RuntimeError("call fit() before publish()")
        return self.registry.publish(
            self.adapter_id, self.adapter,
            meta={"loss": self.last_loss, "steps": int(self.tcfg.steps)})
