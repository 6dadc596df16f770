"""Train/serve step builders: the jit-compiled SPMD programs the launcher
and the multi-pod dry-run lower.

``make_train_step`` returns a donated-state jit function implementing:
  grad(loss) -> [optional int8+EF compressed inter-pod all-reduce]
             -> clip -> AdamW/SGDm -> [optional SR fixed-point weights]

Numerics mode (dense | quant | quant_sparse) comes from the SpringConfig
in ``StepConfig`` — the paper's technique is a first-class switch, not a
fork of the trainer.

Since the RunSpec API landed (DESIGN.md §10), ``StepConfig`` is normally
*produced*, not hand-assembled: ``RunSpec.resolve().step`` (or the
``StepConfig.from_runspec`` convenience below) is the one place the five
config surfaces — SpringConfig, StepConfig, KernelPolicy,
MemstashConfig, serving arguments — are threaded together.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.spring_ops import DENSE, KeyGen, SpringConfig
from repro.kernels.masked_matmul.ops import PROBE_SIZE
from repro.memstash.config import MemstashConfig
from repro.models import encdec as ed_mod
from repro.models import lm as lm_mod
from repro.models.layers import SpringContext
from repro.optim.optimizers import OptimizerConfig, make_optimizer
from repro.runtime.sharding import DEFAULT_RULES, sharding_context


@dataclasses.dataclass(frozen=True)
class StepConfig:
    spring: SpringConfig = DENSE
    prune_ratio: float = 0.0
    optimizer: OptimizerConfig = OptimizerConfig()
    # int8+error-feedback gradient reduction across the 'pod' mesh axis
    compress_pod_grads: bool = False
    microbatch: Optional[int] = None  # gradient accumulation splits
    # logical-sharding rule overrides, e.g. (("seq", (("model",), None)),)
    # = sequence-parallel residual (reduce-scatter TP boundaries)
    rules_override: tuple = ()
    # compressed-activation-stash policy (memstash subsystem); pairs with
    # LMConfig.remat_policy="stash" for the residual stream and drives the
    # per-layer conv/fc stash points in the CNN models
    memstash: MemstashConfig = MemstashConfig()
    # int8 KV cache for serving (SPRING P2 on the cache)
    int8_cache: bool = False

    @classmethod
    def from_runspec(cls, spec) -> "StepConfig":
        """Resolve a :class:`repro.api.RunSpec` (or a spec dict / JSON
        artifact embedding one under a ``"spec"`` key, as every session
        result does) to the StepConfig its run mode implies — the single
        resolution path the launchers use."""
        import json as _json

        from repro.api.spec import RunSpec, SpecError

        if isinstance(spec, str):
            try:
                spec = _json.loads(spec)
            except _json.JSONDecodeError as e:
                raise SpecError(f"invalid spec JSON: {e}") from None
        if isinstance(spec, dict):
            if "run" not in spec and isinstance(spec.get("spec"), dict):
                spec = spec["spec"]  # a run artifact embedding its spec
            spec = RunSpec.from_dict(spec)
        return spec.resolve().step


class TrainState:
    """Pytree train state: params + opt + step + rng (+ EF buffers)."""

    def __init__(self, params, opt_state, step, rng, ef=None):
        self.params, self.opt_state, self.step, self.rng, self.ef = (
            params, opt_state, step, rng, ef,
        )

    def tree_flatten(self):
        return (self.params, self.opt_state, self.step, self.rng, self.ef), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    TrainState, lambda s: s.tree_flatten(), TrainState.tree_unflatten
)


def init_train_state(key, arch, step_cfg: StepConfig, reduced: bool = False):
    cfg = arch.reduced() if reduced else arch.config
    init = ed_mod.encdec_init if arch.is_encdec else lm_mod.lm_init
    params = init(key, cfg)
    opt_init, _ = make_optimizer(step_cfg.optimizer)
    ef = None
    if step_cfg.compress_pod_grads:
        ef = jax.tree_util.tree_map(lambda p: jnp.zeros_like(p, jnp.float32), params)
    return TrainState(params, opt_init(params), jnp.zeros((), jnp.int32), key, ef)


def _loss_for(arch, cfg, params, batch, ctx):
    if arch.is_encdec:
        return ed_mod.encdec_loss(params, cfg, batch["frames"], batch["tokens"], ctx)
    return lm_mod.lm_loss(params, cfg, batch["tokens"], ctx, batch.get("img_embeds"))


def _rules_for(step_cfg: StepConfig):
    if not step_cfg.rules_override:
        return None
    rules = dict(DEFAULT_RULES)
    rules.update(dict(step_cfg.rules_override))
    return rules


def make_train_step(arch, step_cfg: StepConfig, mesh=None, reduced: bool = False,
                    grad_sync=None):
    """Build the SPMD train step.  With ``mesh`` set, logical sharding
    constraints activate and the function is ready to jit with shardings.

    ``grad_sync`` (grads-tree -> grads-tree) runs between the backward
    pass and the optimizer — the seam where spring-mesh splices its
    packed reduce-scatter/all-gather gradient exchange (DESIGN.md §14).
    It composes with the ``compress_pod_grads`` int8+EF pod link, which
    stays where it was (per-pod grads differ; the data-axis exchange
    ``grad_sync`` carries is a different link).

    The step's metrics hold ``mm_tiles`` where the sparsity-aware
    backward is in force: masked_matmul's ``[fwd_issued, fwd_total,
    dx_issued, dx_total, dw_issued, dw_total]`` 128-tile steps and its
    ``[one_dot, total, idle]`` block grid steps of the step, float32 (exact
    below 2**24); MoE configs add ``moe_rows`` (``[live, buffer,
    dropped]`` rows of the held experts, every mode), summed like the
    tiles over microbatches.  The optimizer runs under
    ``jax.named_scope("spring_optimizer")``."""
    cfg = arch.reduced() if reduced else arch.config
    _, opt_update = make_optimizer(step_cfg.optimizer)
    spring_cfg = step_cfg.spring

    # masked_matmul's tile counter: where the sparsity-aware backward is in
    # force, the loss is differentiated against a zero probe as well, whose
    # gradient sums every call's [fwd, dx, dw] x [issued, total] tile steps
    # and its [one_dot, total, idle] block grid steps
    count_tiles = spring_cfg.sparse_backward

    def ctx_for(key, probe=None) -> SpringContext:
        keys = KeyGen(key) if spring_cfg.is_quantized else None
        return SpringContext(cfg=spring_cfg, keys=keys,
                             prune_ratio=step_cfg.prune_ratio,
                             memstash=step_cfg.memstash, tile_probe=probe)

    def value_and_grads(params, batch, key):
        """((loss, metrics), grads, tiles); tiles is None where nothing is
        counted."""
        def loss_fn(p, probe):
            return _loss_for(arch, cfg, p, batch, ctx_for(key, probe))

        if not count_tiles:
            out, grads = jax.value_and_grad(loss_fn, has_aux=True)(params, None)
            return out, grads, None
        out, (grads, tiles) = jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)(
            params, jnp.zeros((PROBE_SIZE,), jnp.float32))
        return out, grads, tiles

    def grads_and_loss(params, batch, key):
        if step_cfg.microbatch is None:
            (loss, metrics), grads, tiles = value_and_grads(params, batch, key)
            return loss, metrics, grads, tiles
        # gradient accumulation over microbatches (memory-bound shapes)
        nm = step_cfg.microbatch

        def body(carry, i):
            acc_loss, acc_grads, acc_tiles, p = carry
            mb = jax.tree_util.tree_map(
                lambda x: x.reshape(nm, x.shape[0] // nm, *x.shape[1:])[i], batch
            )
            (loss, metrics), grads, tiles = value_and_grads(p, mb, jax.random.fold_in(key, i))
            if tiles is not None:
                acc_tiles = acc_tiles + tiles
            return (acc_loss + loss / nm,
                    jax.tree_util.tree_map(lambda a, g: a + g / nm, acc_grads, grads),
                    acc_tiles, p), metrics

        zero_g = jax.tree_util.tree_map(lambda x: jnp.zeros_like(x, jnp.float32), params)
        (loss, grads, tiles, _), metrics = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), zero_g,
                   jnp.zeros((PROBE_SIZE,), jnp.float32), params), jnp.arange(nm)
        )
        # counts sum over the microbatches; every other metric is the last's
        metrics = {name: m.sum(0) if name == "moe_rows" else m[-1]
                   for name, m in metrics.items()}
        return loss, metrics, grads, tiles if count_tiles else None

    def step_metrics(metrics, loss, om, tiles) -> dict:
        metrics = dict(metrics, loss=loss, **om)
        if tiles is not None:
            metrics["mm_tiles"] = tiles
        return metrics

    def plain_step(state: TrainState, batch) -> tuple[TrainState, dict]:
        key = jax.random.fold_in(state.rng, state.step)
        with sharding_context(mesh, _rules_for(step_cfg)):
            loss, metrics, grads, tiles = grads_and_loss(state.params, batch, key)
            if grad_sync is not None:
                grads = grad_sync(grads)
            with jax.named_scope("spring_optimizer"):
                new_p, new_opt, om = opt_update(grads, state.opt_state, state.params,
                                                jax.random.fold_in(key, 0x5eed))
        metrics = step_metrics(metrics, loss, om, tiles)
        return TrainState(new_p, new_opt, state.step + 1, state.rng, state.ef), metrics

    if not step_cfg.compress_pod_grads:
        return plain_step

    assert mesh is not None and "pod" in mesh.shape, "pod axis required for compressed grads"
    from repro.runtime.compression import compressed_allreduce_tree

    def compressed_body(state: TrainState, batch):
        key = jax.random.fold_in(state.rng, state.step)
        with sharding_context(mesh, _rules_for(step_cfg)):
            loss, metrics, grads, tiles = grads_and_loss(state.params, batch, key)
            # int8 + error feedback across pods (per-pod grads differ since
            # each pod saw different data)
            grads, new_ef = compressed_allreduce_tree(
                grads, "pod", jax.random.fold_in(key, 0xc0de), state.ef
            )
            with jax.named_scope("spring_optimizer"):
                new_p, new_opt, om = opt_update(grads, state.opt_state, state.params,
                                                jax.random.fold_in(key, 0x5eed))
        metrics = step_metrics(metrics, loss, om, tiles)
        return TrainState(new_p, new_opt, state.step + 1, state.rng, new_ef), metrics

    def compressed_step(state: TrainState, batch):
        # manual over 'pod' (the compressed link), GSPMD-auto over data/model
        in_specs = (
            jax.tree_util.tree_map(lambda _: P(), state),
            jax.tree_util.tree_map(lambda _: P("pod"), batch),
        )
        out_specs = (
            jax.tree_util.tree_map(lambda _: P(), state),
            P(),
        )
        fn = jax.shard_map(
            compressed_body, mesh=mesh,
            in_specs=in_specs, out_specs=out_specs,
            axis_names={"pod"}, check_vma=False,
        )
        return fn(state, batch)

    return compressed_step


# -- serving ----------------------------------------------------------------
# The serving step builders moved to ``repro.serving.steps`` when the
# continuous-batching engine landed; re-exported here for the dry-run and
# existing callers (lazy to keep runtime <-> serving import-cycle-free).


def make_prefill_step(arch, step_cfg: StepConfig, mesh=None, reduced: bool = False):
    from repro.serving.steps import make_prefill_step as _mk

    return _mk(arch, step_cfg, mesh=mesh, reduced=reduced)


def make_decode_step(arch, step_cfg: StepConfig, mesh=None, reduced: bool = False):
    from repro.serving.steps import make_decode_step as _mk

    return _mk(arch, step_cfg, mesh=mesh, reduced=reduced)
