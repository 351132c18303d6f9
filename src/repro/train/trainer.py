"""Training orchestration: mesh-aware jitted train step (grads -> AdamW ->
router-bias balancing), checkpoint/restart, failure recovery, elastic
re-meshing, straggler monitoring, SDC guard.

``make_train_step(model, tc, ctx)`` is the one step function for both
regimes:

* **single-device** (``ctx`` unmeshed): the smoke/CPU path — ``Model.loss``
  on the full batch, local MoE, plain AdamW.
* **meshed** (``ctx.mesh`` set): params + optimizer state sharded per
  ``parallel/sharding.py`` train rules (FSDP x TP: 128x128-blocked weights
  over the model axis, big dims ZeRO-3 over data), the loss runs TWO
  anti-phase microbatches through one scan (``Model.loss_dual``, paper
  §2.3.1) with the MoE forward/backward dispatched through
  ``ep_flat``/``ep_dedup`` shard_map at the ctx's wire precision (FP8
  dispatch / BF16 combine by default, §3.1), grad-norm clipping uses an
  explicit cross-replica psum (``collectives.sharded_global_norm``), and
  router-bias balancing consumes the EP path's pmean'd per-expert load.

The launcher (launch/train.py), the distributed example, and the fault-
tolerance tests drive the ``Trainer`` class; on a NodeFailure it re-meshes
onto the survivors (``launch.mesh.survivor_mesh``) and restores the last
checkpoint re-sharded onto the new mesh.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import routing
from repro.data.pipeline import SyntheticCorpus
from repro.models.api import Model, build_model
from repro.parallel import collectives
from repro.parallel import context as pctx_mod
from repro.parallel import sharding
from repro.train import checkpoint as ckpt
from repro.train import fault as fault_mod
from repro.train import optimizer as optim
from repro.train import schedule as sched


@dataclasses.dataclass
class TrainConfig:
    peak_lr: float = 3e-4
    warmup: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    bias_update_rate: float = 1e-3        # aux-loss-free balancing (V3)
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    keep_ckpts: int = 3
    sdc_check_every: int = 0              # 0 = off
    seed: int = 0


# families the dual-microbatch scan supports (no encoder/vision memory
# side inputs to thread through the joint scan)
_DUAL_FAMILIES = ("dense", "moe", "ssm", "hybrid")


def dual_microbatch_engaged(cfg: ModelConfig, ctx: pctx_mod.ParallelCtx,
                            batch_size: int) -> bool:
    """Whether the meshed step runs the dual anti-phase microbatch path
    for this (config, ctx, global batch). Single source for the step
    function and the trainer's degradation warning."""
    return (ctx.mesh is not None and ctx.microbatches >= 2
            and cfg.family in _DUAL_FAMILIES
            and batch_size % (2 * ctx.dp_size) == 0)


def _train_rules(cfg: ModelConfig, mesh):
    return sharding.rules_for(cfg, "train",
                              multi_pod="pod" in mesh.axis_names)


def make_train_step(model: Model, tc: TrainConfig,
                    ctx: Optional[pctx_mod.ParallelCtx] = None):
    """Returns jit-able (params, opt_state, batch, step) -> (params,
    opt_state, metrics). Router bias is updated out-of-band (not by Adam)
    per DeepSeek-V3's aux-loss-free balancing.

    ``ctx``: the parallel context threaded into the loss (EP impl, wire
    precision, microbatch overlap). Unmeshed ctx (or None) reproduces the
    single-device step exactly.
    """
    pctx = ctx if ctx is not None else pctx_mod.ParallelCtx()
    # ctx=None keeps the legacy contract: the loss sees whatever context
    # is ambient at trace time (pctx= stays unthreaded)
    thread_ctx = pctx if ctx is not None else None
    meshed = pctx.mesh is not None
    grad_pspecs = None
    if meshed:
        grad_pspecs = sharding.param_pspecs(
            pctx.mesh, model.specs(), _train_rules(model.cfg, pctx.mesh))

    def step_fn(params, opt_state, batch, step):
        B = batch["tokens"].shape[0]
        dual = dual_microbatch_engaged(model.cfg, pctx, B)

        def loss_fn(p):
            if dual:
                # interleaved split: each microbatch keeps rows from every
                # dp shard, so no cross-replica reshard of the halves (a
                # contiguous split would park microbatch A entirely on the
                # low dp ranks). Loss-identical: CE/MTP/load are means,
                # invariant to which rows land in which half.
                bA = {k: v[0::2] for k, v in batch.items()}
                bB = {k: v[1::2] for k, v in batch.items()}
                return model.loss_dual(p, bA, bB, pctx=thread_ctx)
            return model.loss(p, batch, pctx=thread_ctx)

        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        gnorm = None
        if meshed:
            # explicit cross-replica psum of the squared grad norm — the
            # clip scale is collective-exact, not GSPMD-placed
            gnorm = collectives.sharded_global_norm(
                grads, pctx.mesh, grad_pspecs)
        lr = sched.warmup_cosine(step, peak_lr=tc.peak_lr, warmup=tc.warmup,
                                 total=tc.total_steps)
        params, opt_state, ostats = optim.update(
            grads, opt_state, params, lr=lr,
            weight_decay=tc.weight_decay, clip_norm=tc.clip_norm,
            grad_norm=gnorm)
        # --- aux-loss-free router-bias balancing (paper T2/V3) ----------
        # per-expert load arrives cross-replica reduced: the EP path
        # pmeans it over the dp x model mesh inside shard_map
        cfg = model.cfg
        if cfg.moe and cfg.moe.router_bias:
            for seg in model.segments:
                key = f"{seg.name}/load_layers"
                if key in metrics and "moe" in params[seg.name]:
                    load = metrics[key]                      # (n, E)
                    bias = params[seg.name]["moe"]["bias"]
                    new_bias = routing.update_bias(
                        bias, load, tc.bias_update_rate)
                    params[seg.name]["moe"]["bias"] = new_bias
                    # keep master copy consistent
                    opt_state = opt_state._replace(master=_set_in(
                        opt_state.master, (seg.name, "moe", "bias"),
                        new_bias.astype(jnp.float32)))
        metrics = {k: v for k, v in metrics.items()
                   if not k.endswith("load_layers")}
        metrics.update(ostats)
        metrics["loss"] = loss
        return params, opt_state, metrics

    return step_fn


def _set_in(tree, path, value):
    if len(path) == 1:
        out = dict(tree)
        out[path[0]] = value
        return out
    out = dict(tree)
    out[path[0]] = _set_in(tree[path[0]], path[1:], value)
    return out


class Trainer:
    """Single-process trainer with restart/elastic-recovery semantics.

    ``ctx`` (a ``ParallelCtx``; defaults to the ambient context) selects
    the regime: with a mesh, params/opt state are initialized sharded,
    batches are placed over the dp axes, and the step function is the
    meshed dual-microbatch EP step. On a ``NodeFailure`` the device pool
    shrinks (``survivor_mesh`` halves the dp axis), and training resumes
    from the last checkpoint re-sharded onto the survivor mesh."""

    def __init__(self, cfg: ModelConfig, tc: TrainConfig,
                 data: Optional[SyntheticCorpus] = None,
                 injector: Optional[fault_mod.FailureInjector] = None,
                 global_batch: int = 8, seq_len: int = 64,
                 ctx: Optional[pctx_mod.ParallelCtx] = None):
        self.cfg = cfg
        self.tc = tc
        self.model = build_model(cfg)
        self.data = data or SyntheticCorpus(cfg.vocab_size, seq_len,
                                            global_batch, seed=tc.seed)
        self.injector = injector
        self.ctx = ctx if ctx is not None else pctx_mod.get()
        self.sdc = fault_mod.SDCGuard()
        self.straggler = fault_mod.StragglerMonitor(
            n_replicas=self._n_replicas())
        self.restarts = 0
        self.history: list = []
        self.last_device_checksums: Dict[int, int] = {}
        self._init_state()

    # -- mesh plumbing -------------------------------------------------------
    @property
    def meshed(self) -> bool:
        return self.ctx.mesh is not None

    def _n_replicas(self) -> int:
        return self.ctx.dp_size if self.meshed else 1

    def _state_shardings(self):
        mesh = self.ctx.mesh
        pshard, oshard, _ = sharding.train_state_shardings(
            mesh, self.model.specs(), _train_rules(self.cfg, mesh))
        return {"params": pshard, "opt": oshard}

    def _batch_sharding(self, batch):
        from jax.sharding import NamedSharding
        mesh = self.ctx.mesh
        pspec = sharding.batch_pspec(mesh, batch["tokens"].shape[0],
                                     self.ctx.dp_axes)
        return NamedSharding(mesh, pspec)

    def _remesh_on_failure(self):
        """Shrink to the survivor mesh; EP/model axis preserved."""
        if not self.meshed:
            return
        from repro.launch.mesh import survivor_mesh
        new_mesh = survivor_mesh(self.ctx.mesh)
        if new_mesh is not self.ctx.mesh:
            self.ctx = dataclasses.replace(self.ctx, mesh=new_mesh)
        self.straggler = fault_mod.StragglerMonitor(
            n_replicas=self._n_replicas())

    # -- state ---------------------------------------------------------------
    def _init_state(self, restore: bool = False):
        rng = jax.random.PRNGKey(self.tc.seed)
        shardings = self._state_shardings() if self.meshed else None
        if restore and self.tc.ckpt_dir and ckpt.latest_step(self.tc.ckpt_dir):
            # structure only — eval_shape materializes nothing; restore
            # device_puts each logical array onto the (possibly survivor)
            # mesh's shardings: the elastic re-shard
            like = jax.eval_shape(
                lambda r: (lambda p: {"params": p, "opt": optim.init(p)})(
                    self.model.init(r)), rng)
            state, extras = ckpt.restore(self.tc.ckpt_dir, like,
                                         shardings=shardings)
            self.params = state["params"]
            self.opt_state = state["opt"]
            self.step = int(extras["step"])
        else:
            # sharded trainers draw each device's shard in place
            self.params = self.model.init(
                rng, shardings["params"] if self.meshed else None)
            # repro-lint: disable=R2-jit-contract -- the params are read,
            # not consumed: the optimizer state is built beside them
            self.opt_state = jax.jit(
                optim.init,
                out_shardings=shardings["opt"] if self.meshed else None)(
                    self.params)
            self.step = 0
        # donate params + opt state so the update happens in place —
        # without it the fp32 master + bf16 m/v live twice per step,
        # blowing the 10-byte/param budget
        self._jit_step = jax.jit(
            make_train_step(self.model, self.tc, self.ctx),
            donate_argnums=(0, 1))
        # first dispatches after a (re)jit pay compilation + first-run
        # allocation — not steady-state timings; don't let them poison
        # the straggler EWMA
        self._warmup_steps = 2
        # surface silent degradations instead of leaving the user to
        # believe the requested overlap is active
        gb = getattr(self.data, "batch", None)
        if gb is None:   # duck-typed corpus: only batch_at is guaranteed
            gb = self.data.batch_at(0)["tokens"].shape[0]
        if (self.meshed and self.ctx.microbatches >= 2
                and not dual_microbatch_engaged(self.cfg, self.ctx, gb)):
            import warnings
            warnings.warn(
                f"dual-microbatch overlap requested but not engaged: "
                f"family={self.cfg.family} needs to be one of "
                f"{_DUAL_FAMILIES} and global batch {gb} must be a "
                f"multiple of 2*dp={2 * self.ctx.dp_size}; running the "
                f"single-batch step", stacklevel=2)

    def _save(self):
        if self.tc.ckpt_dir:
            extras = {"step": self.step}
            if self.meshed:
                mesh = self.ctx.mesh
                extras["mesh"] = {"axes": list(mesh.axis_names),
                                  "shape": [int(mesh.shape[a])
                                            for a in mesh.axis_names]}
            ckpt.save(self.tc.ckpt_dir, self.step,
                      {"params": self.params, "opt": self.opt_state},
                      extras=extras, keep=self.tc.keep_ckpts)

    def run(self, steps: int) -> Dict[str, Any]:
        target = self.step + steps
        while self.step < target:
            try:
                self._run_until(target)
            except fault_mod.NodeFailure:
                # failure: re-mesh on survivors + restore last checkpoint,
                # re-sharded onto the shrunken mesh
                self.restarts += 1
                self._remesh_on_failure()
                self._init_state(restore=True)
        return {"final_step": self.step, "restarts": self.restarts,
                "history": self.history,
                "sdc_alarms": self.sdc.alarms,
                "straggler_events": self.straggler.events,
                "mesh_shape": (tuple(int(self.ctx.mesh.shape[a])
                                     for a in self.ctx.mesh.axis_names)
                               if self.meshed else None)}

    # -- measurement ---------------------------------------------------------
    def _observe_step(self, metrics, t0: float) -> None:
        """Per-replica step-time observation. Meshed: real per-shard
        completion times off the device mesh; unmeshed: the single
        process is the only replica."""
        if self.meshed:
            times = fault_mod.replica_step_times(
                metrics["loss"], self.ctx.mesh, self.ctx.dp_axes, t0)
        else:
            # repro-lint: disable=R1-host-sync -- step-time observation
            # IS the sync: once per step, outside the jitted step fn
            jax.block_until_ready(metrics["loss"])
            times = [time.perf_counter() - t0]
        if self._warmup_steps > 0:
            self._warmup_steps -= 1
            if self.injector and self.injector.slow_replica(
                    self.step) is not None:
                import warnings
                warnings.warn(f"slow-replica injection at step {self.step} "
                              f"falls in the post-jit warmup window and is "
                              f"not observed", stacklevel=2)
            return
        slow = (self.injector.slow_replica(self.step)
                if self.injector else None)
        if slow is not None and slow < len(times):
            times[slow] *= self.injector.slow_factor
        self.straggler.observe(self.step, times)

    def _sdc_checksums(self) -> list:
        """Checksums whose disagreement flags silent corruption.

        Meshed: every fully-replicated param leaf (router biases, norms,
        any non-divisible tensor) is bit-identical on every device by
        construction, so each device's checksum of its replicated copies
        is a real cross-replica comparison — a bit persistently flipped
        in one device's memory diverges that device's entry (paper §6.1;
        the sharded leaves are covered at checkpoint granularity by the
        manifest CRCs). Falls back to two independent full read-backs
        (transient/readback corruption) if nothing is replicated.
        Unmeshed: the on-device checksum vs a simulated second replica."""
        if self.meshed:
            repl = [l for l in jax.tree.leaves(self.params)
                    if getattr(l.sharding, "is_fully_replicated", False)]
            if repl:
                per_dev = collectives.device_checksums(repl)
                self.last_device_checksums = per_dev
                checks = [per_dev[d] for d in sorted(per_dev)]
            else:
                read1 = collectives.device_checksums(self.params)
                read2 = collectives.device_checksums(self.params)
                self.last_device_checksums = read2
                checks = [
                    functools.reduce(lambda a, b: a ^ b, r.values(), 0)
                    for r in (read1, read2)]
        else:
            c = int(collectives.tree_checksum(self.params))
            checks = [c, c]     # DP replicas (bit-identical here)
        if self.injector and self.injector.corrupts(self.step):
            checks[1] ^= 0xDEAD
            self.injector.fired.add(self.step)
        return checks

    def _run_until(self, target: int):
        while self.step < target:
            if self.injector:
                self.injector.check(self.step)
            batch = {k: jnp.asarray(v)
                     for k, v in self.data.batch_at(self.step).items()}
            if self.meshed:
                # repro-lint: disable=R1-host-sync -- the input
                # pipeline's one H2D feed per step, an accounted
                # crossing (overlapped by the prefetcher, not the tier)
                batch = jax.device_put(batch, self._batch_sharding(batch))
            t0 = time.perf_counter()
            self.params, self.opt_state, metrics = self._jit_step(
                self.params, self.opt_state, batch, jnp.asarray(self.step))
            self._observe_step(metrics, t0)
            metrics = {k: (float(v) if getattr(v, "ndim", 1) == 0 else
                           np.asarray(v))
                       for k, v in metrics.items()}
            self.history.append({"step": self.step, **{
                k: v for k, v in metrics.items() if np.ndim(v) == 0}})
            self.step += 1
            if self.tc.sdc_check_every and \
                    self.step % self.tc.sdc_check_every == 0:
                if not self.sdc.check(self.step, self._sdc_checksums()):
                    self._init_state(restore=True)    # restore-on-SDC
                    continue
            if self.tc.ckpt_dir and self.step % self.tc.ckpt_every == 0:
                self._save()
