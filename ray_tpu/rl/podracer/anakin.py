"""Anakin: env step + learner update fused into ONE jitted program.

Reference: "Podracer architectures for scalable Reinforcement Learning"
(PAPERS.md) §2 — when the environment itself is jittable (jax_env.py
protocol), the fastest architecture keeps EVERYTHING on the accelerator:
each mesh slice steps a batch of envs, unrolls a rollout with lax.scan,
computes the V-trace actor-critic update and applies pmean'd gradients,
all inside one XLA program per iteration. Zero hosts in the loop, zero
object-store traffic, zero dispatches — the control plane only launches
the compiled computation.

Built over ``ray_tpu.parallel`` shard_map (the repo's mesh substrate):
env state/obs shard over the ``dp`` axis, params/optimizer state stay
replicated (gradients are ``lax.pmean``'d across ``dp``, so every device
applies the identical update — the pmap idiom, expressed over the mesh).
On-policy V-trace degenerates to n-step actor-critic (importance ratios
are 1), so Sebulba and Anakin share one loss implementation
(rl/impala.py vtrace)."""
from __future__ import annotations

import dataclasses
import time
from typing import Any

import numpy as np

from ..impala import ImpalaConfig
from . import telemetry as tm
from .jax_env import JaxCartPole


@dataclasses.dataclass
class AnakinConfig:
    """Anakin knobs. ``env`` must follow the jax_env.py protocol
    (pure reset/step, auto-reset on done)."""

    env: Any = dataclasses.field(default_factory=JaxCartPole)
    batch_per_device: int = 32    # vectorized envs per mesh slice
    rollout_len: int = 16
    hidden: tuple = (64, 64)
    seed: int = 0
    impala: ImpalaConfig = dataclasses.field(default_factory=ImpalaConfig)
    mesh: Any = None              # jax Mesh with a dp axis; None = all
    #                               devices on dp (build_mesh(dp=-1))


class AnakinTrainer:
    """The fused trainer: ``train()`` = one jitted shard_map call."""

    def __init__(self, config: AnakinConfig):
        import jax
        import optax
        from ...core.usage import record_library_usage
        from ...parallel import MeshSpec, build_mesh
        from .. import module as module_lib
        record_library_usage("rl.podracer")
        self.config = config
        self.env = config.env
        self.mesh = config.mesh if config.mesh is not None \
            else build_mesh(MeshSpec(dp=-1, keep_unit_axes=False))
        if "dp" not in self.mesh.axis_names:
            raise ValueError("Anakin needs a mesh with a 'dp' axis")
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        if int(np.prod(self.mesh.devices.shape)) != sizes["dp"]:
            raise ValueError(
                "Anakin shards envs over dp only; other mesh axes must "
                f"be size 1, got {sizes}")
        self._num_devices = sizes["dp"]
        self.module_cfg = module_lib.MLPConfig(
            obs_dim=self.env.obs_dim, num_actions=self.env.num_actions,
            hidden=tuple(config.hidden))
        key = jax.random.PRNGKey(config.seed)
        key, pkey = jax.random.split(key)
        self.params = module_lib.init(pkey, self.module_cfg)
        cfg = config.impala
        self.optimizer = optax.chain(
            optax.clip_by_global_norm(cfg.grad_clip),
            optax.rmsprop(cfg.lr, decay=0.99, eps=0.1))
        self.opt_state = self.optimizer.init(self.params)
        self._init_env_state(key)
        self._run = self._build_run()
        self.iteration = 0
        self._total_env_steps = 0
        # trailing (return_sum, episode_count) pairs for the mean window
        self._ret_window: list[tuple[float, float]] = []

    def _init_env_state(self, key) -> None:
        import jax
        n = self._num_devices * self.config.batch_per_device
        key, ekey, *dkeys = jax.random.split(key, 2 + self._num_devices)
        self._env_state, self._obs = jax.vmap(self.env.reset)(
            jax.random.split(ekey, n))
        self._keys = jax.numpy.stack(dkeys)        # [D, 2] one per device
        import jax.numpy as jnp
        self._ep_ret = jnp.zeros((n,), jnp.float32)

    def _build_run(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from .. import module as module_lib
        from ..impala import vtrace
        env, cfg = self.env, self.config.impala
        T = self.config.rollout_len
        optimizer = self.optimizer

        def device_fn(params, opt_state, env_state, obs, key, ep_ret):
            key = key[0]     # [1, 2] shard of the per-device key stack

            def step_fn(carry, _):
                env_state, obs, key, ep_ret, csum, cnt = carry
                key, sub = jax.random.split(key)
                action, logp, value = module_lib.sample_action(
                    params, obs, sub)
                env_state, next_obs, reward, done = jax.vmap(env.step)(
                    env_state, action)
                ep_ret = ep_ret + reward
                csum = csum + jnp.sum(jnp.where(done, ep_ret, 0.0))
                cnt = cnt + jnp.sum(done.astype(jnp.float32))
                ep_ret = jnp.where(done, 0.0, ep_ret)
                carry = (env_state, next_obs, key, ep_ret, csum, cnt)
                return carry, (obs, action, logp, value, reward, done)

            (env_state, obs, key, ep_ret, csum, cnt), traj = jax.lax.scan(
                step_fn,
                (env_state, obs, key, ep_ret,
                 jnp.zeros(()), jnp.zeros(())),
                None, length=T)
            t_obs, t_act, t_logp, _t_val, t_rew, t_done = traj
            bootstrap = module_lib.logits_and_value(params, obs)[1]

            def loss_fn(p):
                logits, values = module_lib.logits_and_value(p, t_obs)
                logp_all = jax.nn.log_softmax(logits, axis=-1)
                target_logp = jnp.take_along_axis(
                    logp_all, t_act[..., None], axis=-1)[..., 0]
                # on-policy: behaviour == target, so the V-trace ratios
                # are 1 and this is n-step actor-critic — one loss shared
                # with the Sebulba/IMPALA learner
                vs, pg_adv = vtrace(
                    jax.lax.stop_gradient(t_logp), target_logp, t_rew,
                    values, t_done.astype(jnp.float32), bootstrap,
                    cfg.gamma, cfg.rho_bar, cfg.c_bar)
                pg_loss = -jnp.mean(target_logp * pg_adv)
                vf_loss = 0.5 * jnp.mean((vs - values) ** 2)
                entropy = -jnp.mean(
                    jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1))
                total = (pg_loss + cfg.vf_coeff * vf_loss
                         - cfg.entropy_coeff * entropy)
                return total, (pg_loss, vf_loss, entropy)

            (loss, (pg, vf, ent)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            grads = jax.lax.pmean(grads, "dp")
            updates, opt_state = optimizer.update(grads, opt_state,
                                                  params)
            import optax as _optax
            params = _optax.apply_updates(params, updates)
            metrics = {
                "loss": jax.lax.pmean(loss, "dp"),
                "pg_loss": jax.lax.pmean(pg, "dp"),
                "vf_loss": jax.lax.pmean(vf, "dp"),
                "entropy": jax.lax.pmean(ent, "dp"),
                "return_sum": jax.lax.psum(csum, "dp"),
                "episodes": jax.lax.psum(cnt, "dp"),
            }
            return (params, opt_state, env_state, obs, key[None],
                    ep_ret, metrics)

        fn = jax.shard_map(
            device_fn, mesh=self.mesh,
            in_specs=(P(), P(), P("dp"), P("dp"), P("dp"), P("dp")),
            out_specs=(P(), P(), P("dp"), P("dp"), P("dp"), P("dp"),
                       P()),
            check_vma=False)
        return jax.jit(fn)

    def train(self) -> dict:
        """One iteration = one compiled program: rollout_len fused
        env-step/sample steps on every device, one pmean'd update."""
        t0 = time.perf_counter()
        (self.params, self.opt_state, self._env_state, self._obs,
         self._keys, self._ep_ret, metrics) = self._run(
            self.params, self.opt_state, self._env_state, self._obs,
            self._keys, self._ep_ret)
        metrics = {k: float(v) for k, v in metrics.items()}
        dt = time.perf_counter() - t0
        steps = (self._num_devices * self.config.batch_per_device
                 * self.config.rollout_len)
        self._total_env_steps += steps
        self.iteration += 1
        self._ret_window.append(
            (metrics.pop("return_sum"), metrics.pop("episodes")))
        self._ret_window = self._ret_window[-20:]
        ret_sum = sum(s for s, _ in self._ret_window)
        ret_n = sum(n for _, n in self._ret_window)
        try:
            tm.env_steps().inc(float(steps), tags={"arch": "anakin"})
            tm.learner_update().observe(dt, tags={"arch": "anakin"})
        except Exception:
            pass  # telemetry must never fail training
        return {
            "training_iteration": self.iteration,
            "episode_return_mean": (ret_sum / ret_n if ret_n
                                    else float("nan")),
            "num_env_steps_sampled_lifetime": self._total_env_steps,
            "env_steps_per_sec": steps / max(dt, 1e-9),
            "num_devices": self._num_devices,
            **{f"learner/{k}": v for k, v in metrics.items()},
        }

    def flops_estimate(self):
        """FLOPs of one fused iteration via XLA cost_analysis on the
        compiled program (one extra out-of-band compile; the MFU input
        for PodracerTrainer(profile=True) and the ROADMAP TPU goal)."""
        from ...util.profiling import compiled_flops
        return compiled_flops(self._run, self.params, self.opt_state,
                              self._env_state, self._obs, self._keys,
                              self._ep_ret)

    # -- checkpoint ------------------------------------------------------ #

    def save_state(self) -> dict:
        import jax
        return {"params": jax.device_get(self.params),
                "opt_state": jax.device_get(self.opt_state),
                "iteration": self.iteration,
                "total_env_steps": self._total_env_steps}

    def restore_state(self, state: dict) -> None:
        import jax
        import jax.numpy as jnp
        self.params = jax.tree.map(jnp.asarray, state["params"])
        self.opt_state = jax.tree.map(jnp.asarray, state["opt_state"])
        self.iteration = int(state["iteration"])
        self._total_env_steps = int(state["total_env_steps"])

    def stop(self) -> None:
        pass  # no actors, no channels: nothing to tear down
