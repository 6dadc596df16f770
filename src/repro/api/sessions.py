"""Sessions: one object per execution mode, all driven by one RunSpec.

``TrainSession`` / ``ServeSession`` / ``DryrunSession`` own what the
launchers used to assemble by hand — jit program building, the data
stream, checkpointing, metrics sinks — and every ``run()`` result embeds
the canonical resolved spec (``spec`` / ``spec_hash`` / ``provenance``)
so any run is reproducible from one artifact.

The launchers (``repro.launch.train|serve|dryrun``) and examples are thin
adapters: parse ``--spec`` + ``--set`` (+ deprecated legacy flags), build
the RunSpec, hand it to :func:`session_for`.

The session bodies are verbatim ports of the pre-RunSpec launcher loops;
the serving parity suite (tests/test_serving.py) and the checkpoint
determinism tests (tests/test_system.py) seal them bit-for-bit.
"""

import dataclasses
import json
import logging
import sys
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.api.spec import RunSpec, SpecError, build_spec
from repro.configs import SHAPES

log = logging.getLogger("repro.train")


class Session:
    """Base: resolve the spec once, expose the reproducibility payload."""

    run_mode: str = ""

    def __init__(self, spec: RunSpec, *, mesh=None):
        if self.run_mode and spec.run != self.run_mode:
            raise SpecError(
                f"{type(self).__name__} needs a run={self.run_mode!r} spec, "
                f"got run={spec.run!r}")
        self.spec = spec
        self.resolved = spec.resolve()
        if mesh is None and spec.shape.mesh.explicit:
            # spring-mesh: an explicit topology in the spec builds its
            # own mesh (DESIGN.md §14); a caller-passed mesh still wins
            mesh = build_mesh(spec.shape.mesh)
        self.mesh = mesh

    def trace_path(self) -> str:
        """Effective Chrome-trace output path ('' = telemetry off).
        An enabled spec with no explicit path still writes a trace — the
        acceptance contract is that flipping ``telemetry.enabled`` alone
        yields a Perfetto-loadable artifact."""
        t = self.spec.telemetry
        if not t.enabled:
            return ""
        return t.trace_path or f"spring_{self.spec.run}_trace.json"

    def telemetry_scope(self):
        """Ambient spring-trace scope for this run (no-op when disabled);
        session bodies run inside it so engine/kernel/memstash spans land
        in one tracer, written to :meth:`trace_path` on exit."""
        t = self.spec.telemetry
        cfg = telemetry.TelemetryConfig(
            enabled=t.enabled, trace_path=self.trace_path(),
            sample_rate=t.sample_rate)
        return telemetry.scope(cfg, metadata={
            "run": self.spec.run, "spec_hash": self.spec.spec_hash()})

    def _with_payload(self, out: dict) -> dict:
        out.update(self.spec.payload())
        if self.spec.telemetry.enabled:
            tr = telemetry.tracer()
            out["telemetry"] = {
                "metrics": telemetry.metrics().snapshot(),
                "trace_path": self.trace_path(),
                "sample_rate": self.spec.telemetry.sample_rate,
                "spans": len(tr) if tr is not None else 0,
            }
        return out


class TrainSession(Session):
    """End-to-end training driver: data -> train_step -> checkpoint ->
    resume, with the straggler watchdog and loss metrics sink."""

    run_mode = "train"

    def run(self) -> dict:
        with self.telemetry_scope():
            return self._run_body()

    def _run_body(self) -> dict:
        from repro.checkpoint import CheckpointManager
        from repro.data.pipeline import DataConfig, SyntheticLMStream
        from repro.runtime.resilience import StragglerWatchdog
        from repro.runtime.train import TrainState, init_train_state, make_train_step

        spec, r = self.spec, self.resolved
        cfg, step_cfg, view = r.config, r.step, r.view
        seed = spec.seeds.seed

        data = SyntheticLMStream(DataConfig(
            seed=seed, vocab=cfg.vocab, seq_len=spec.shape.seq,
            global_batch=spec.shape.batch))
        state = init_train_state(jax.random.PRNGKey(seed), view, step_cfg,
                                 reduced=True)
        start_step = 0

        manager = (CheckpointManager(spec.train.ckpt_dir,
                                     every_steps=spec.train.ckpt_every)
                   if spec.train.ckpt_dir else None)
        if manager is not None:
            restored = manager.restore_or_none()
            if restored is not None:
                start_step, tree = restored
                state = TrainState(*tree)
                log.info("resumed from step %d", start_step)

        sharded = spec.shape.mesh.data > 1 and self.mesh is not None
        if sharded:
            # spring-mesh: packed-collective data parallelism — gradients
            # cross the wire binary-mask compressed, losses stay
            # bit-identical to the single-device oracle (DESIGN.md §14)
            from repro.dist.train import make_sharded_train_step

            step_fn = jax.jit(
                make_sharded_train_step(view, step_cfg, self.mesh),
                donate_argnums=(0,))
        else:
            step_fn = jax.jit(make_train_step(view, step_cfg, mesh=self.mesh),
                              donate_argnums=(0,))
        watchdog = StragglerWatchdog()
        losses = []
        steps = spec.train.steps
        meta = {"arch": spec.arch.id, "mode": spec.numerics.mode,
                "spec_hash": spec.spec_hash()}
        for step in range(start_step, steps):
            with telemetry.span("train.step", step=step):
                with telemetry.span("train.step.data"):
                    tokens = data.batch(step)
                watchdog.step_start()
                with telemetry.span("train.step.device"):
                    state, metrics = step_fn(state, {"tokens": tokens})
                    if telemetry.enabled():
                        # pin dispatch+compute inside the device span so
                        # the host span measures host work only; changes
                        # when we wait, never what is computed
                        jax.block_until_ready(metrics)
                with telemetry.span("train.step.host"):
                    loss = float(metrics["loss"])
                    watchdog.step_end(step)
                    losses.append(loss)
                    if step % spec.train.log_every == 0 or step == steps - 1:
                        log.info("step %d loss %.4f grad_norm %.3f", step,
                                 loss, float(metrics["grad_norm"]))
                    if manager is not None:
                        manager.maybe_save(step + 1,
                                           tuple(state.tree_flatten()[0]),
                                           meta)
        if manager is not None:
            manager.maybe_save(steps, tuple(state.tree_flatten()[0]), meta,
                               force=True)
        out = {
            "first_loss": losses[0] if losses else None,
            "last_loss": losses[-1] if losses else None,
            "losses": losses,
            # wall seconds per step, device work included (the loss read
            # syncs); the first step of a run also pays its compile
            "step_s": [e.duration for e in watchdog.events],
            "slow_steps": sum(1 for e in watchdog.events if e.slow),
            "state": state,
            "mesh": spec.shape.mesh.label(),
        }
        if sharded:
            # measured wire accounting of one packed exchange at the
            # probe density (the jitted path's hooks are trace-inert)
            from repro.dist.collectives import collective_probe

            out["collective_probe"] = collective_probe(
                spec.sparsity.probe_density, world=spec.shape.mesh.data)
        return self._with_payload(out)


# -- serving ----------------------------------------------------------------


def synthetic_batch(arch, cfg, batch: int, prompt_len: int, key) -> dict:
    """The serving sessions' stand-in traffic (same construction the
    static path always used, so engine/static parity runs on identical
    prompts)."""
    if arch.is_encdec:
        return {
            "frames": jax.random.normal(key, (batch, cfg.enc_seq, cfg.d_model),
                                        jnp.bfloat16),
            "tokens": jax.random.randint(key, (batch, prompt_len), 0, cfg.vocab),
        }
    out = {"tokens": jax.random.randint(key, (batch, prompt_len), 0, cfg.vocab)}
    if cfg.vlm_prefix_len:
        out["img_embeds"] = jax.random.normal(
            key, (batch, cfg.vlm_prefix_len, cfg.d_model), jnp.bfloat16)
    return out


class ServeSession(Session):
    """One-shot serving session: the continuous-batching engine (or, for
    encoder-decoder archs and ``serving.static`` specs, the pre-engine
    static reference path kept as the parity oracle)."""

    run_mode = "serve"

    def __init__(self, spec: RunSpec, *, mesh=None, params=None):
        super().__init__(spec, mesh=mesh)
        self.params = params

    def run(self) -> dict:
        with self.telemetry_scope():
            arch = self.resolved.arch
            if self.spec.serving.static or arch.is_encdec:
                # encoder-decoder archs keep the static loop (DESIGN.md §9)
                return self._with_payload(self._static())
            return self._with_payload(self._engine())

    def _static(self) -> dict:
        """The pre-engine static path: one fixed batch, prefill once,
        decode ``gen`` steps, throw the cache away.  Kept verbatim as the
        parity oracle the engine is sealed against."""
        from repro.serving.steps import make_decode_step, make_prefill_step

        spec, r = self.spec, self.resolved
        arch, view, cfg, step_cfg = r.arch, r.view, r.config, r.step
        batch, prompt_len, gen = (spec.shape.batch, spec.shape.prompt_len,
                                  spec.shape.gen)
        key = jax.random.PRNGKey(spec.seeds.seed)

        from repro.models import encdec as ed_mod
        from repro.models import lm as lm_mod

        init = ed_mod.encdec_init if arch.is_encdec else lm_mod.lm_init
        params = self.params if self.params is not None else init(key, cfg)
        batch_inputs = synthetic_batch(arch, cfg, batch, prompt_len, key)

        sharded = (spec.shape.mesh.data > 1 and self.mesh is not None
                   and not arch.is_encdec)
        if sharded and batch % spec.shape.mesh.data:
            # indivisible request batch: replicate instead of sharding,
            # and say so through the same fallback counter the logical
            # rules use (satellite of DESIGN.md §14)
            from repro.runtime.sharding import note_mesh_fallback

            note_mesh_fallback("serve_batch")
            sharded = False
        if sharded:
            # spring-mesh: rows sharded over the data axis, logits cross
            # the wire binary-mask packed (DESIGN.md §14)
            from repro.dist.serve import (make_sharded_decode_step,
                                          make_sharded_prefill_step)

            prefill = jax.jit(make_sharded_prefill_step(
                view, step_cfg, self.mesh, reduced=True))
            decode = jax.jit(make_sharded_decode_step(
                view, step_cfg, self.mesh, reduced=True))
        else:
            prefill = jax.jit(make_prefill_step(view, step_cfg, mesh=self.mesh,
                                                reduced=True))
            decode = jax.jit(make_decode_step(view, step_cfg, mesh=self.mesh,
                                              reduced=True))

        t0 = time.monotonic()
        if arch.is_encdec:
            from repro.models.layers import SpringContext

            cache = ed_mod.encdec_init_cache(params, cfg, batch_inputs["frames"],
                                             SpringContext(),
                                             max_len=prompt_len + gen)
            logits = jnp.zeros((batch, cfg.vocab))
            next_tok = batch_inputs["tokens"][:, 0]
        else:
            # decode continues past the prompt: extend the cache buffers
            from repro.models.lm import pad_cache

            logits, cache = prefill(params, batch_inputs, key)
            cache = pad_cache(cache, gen)
            next_tok = jnp.argmax(logits, -1)
        t_prefill = time.monotonic() - t0

        tokens_out = []
        t0 = time.monotonic()
        for i in range(gen):
            logits, cache = decode(params, next_tok, cache,
                                   jax.random.fold_in(key, i))
            next_tok = (jnp.argmax(logits, -1) if spec.serving.greedy
                        else jax.random.categorical(
                            jax.random.fold_in(key, 1000 + i), logits))
            tokens_out.append(next_tok)
        jax.block_until_ready(logits)
        t_decode = time.monotonic() - t0

        seqs = jnp.stack(tokens_out, axis=1)
        out = {
            "generated": seqs,
            "prefill_s": t_prefill,
            "decode_s": t_decode,
            "tokens_per_s": batch * gen / t_decode if t_decode else 0.0,
            "finite": bool(jnp.all(jnp.isfinite(logits))),
            "engine": False,
            "mesh": spec.shape.mesh.label(),
        }
        if sharded:
            from repro.dist.collectives import collective_probe

            out["collective_probe"] = collective_probe(
                spec.sparsity.probe_density, world=spec.shape.mesh.data)
        return out

    def _engine(self) -> dict:
        from repro.serving.engine import ServingEngine

        spec, r = self.spec, self.resolved
        arch, cfg = r.arch, r.config
        batch, prompt_len, gen = (spec.shape.batch, spec.shape.prompt_len,
                                  spec.shape.gen)
        # None means "default to batch" (the engine's from_spec applies
        # the same rule to slots; an explicit 0 must reach the engine's
        # own validation rather than being silently replaced)
        queue = spec.serving.queue
        n_requests = batch if queue is None else queue
        seed = spec.seeds.seed
        key = jax.random.PRNGKey(seed)

        from repro.models.lm import lm_init

        params = (self.params if self.params is not None
                  else lm_init(key, cfg))
        # queued requests beyond the first batch reuse the synthetic
        # construction with a folded key (distinct prompts, reproducible)
        prompts = []
        img = []
        for chunk in range((n_requests + batch - 1) // batch):
            bi = synthetic_batch(arch, cfg, batch, prompt_len,
                                 jax.random.fold_in(key, chunk) if chunk else key)
            for b in range(batch):
                prompts.append([int(t) for t in bi["tokens"][b]])
                img.append(bi.get("img_embeds")[b] if "img_embeds" in bi else None)
        prompts, img = prompts[:n_requests], img[:n_requests]

        engine = ServingEngine.from_spec(spec, params=params, mesh=self.mesh,
                                         resolved=r)
        if spec.serving.restore_path:
            # spring-survive resume: drain a saved snapshot's in-flight
            # work instead of submitting fresh requests — the restored
            # engine emits the exact remaining tokens of every request
            engine.restore_file(spec.serving.restore_path)
        else:
            for i, p in enumerate(prompts):
                engine.submit_prompt(p, gen, seed=seed + i, img_embeds=img[i])
        out = engine.run()
        # token lists may be ragged (EOS finishes / typed rejections):
        # stack only the uniform case, keep exact lists otherwise
        tok_lists = [req["tokens"] for req in out["per_request"]]
        lens = {len(t) for t in tok_lists}
        out["generated"] = (jnp.asarray(tok_lists, jnp.int32)
                            if len(lens) == 1 else tok_lists)
        out["engine"] = True
        out["slots"] = engine.n_slots
        out["mode"] = spec.numerics.mode
        return out


# -- dryrun -----------------------------------------------------------------


def build_mesh(mesh):
    """Mesh from a ``MeshSpec`` (or legacy kind string).  Explicit axis
    extents take precedence over ``kind`` (DESIGN.md §14)."""
    from repro.launch.mesh import make_debug_mesh, make_production_mesh

    kind = mesh
    if not isinstance(mesh, str):
        if mesh.explicit:
            from repro.dist.mesh import make_explicit_mesh

            return make_explicit_mesh(mesh.pod, mesh.data, mesh.model)
        kind = mesh.kind
    if kind == "single":
        return make_production_mesh(multi_pod=False)
    if kind == "multi":
        return make_production_mesh(multi_pod=True)
    if kind == "debug":
        return make_debug_mesh()
    if kind == "debug_multi":
        return make_debug_mesh(multi_pod=True)
    raise ValueError(kind)


def _param_counts(arch) -> tuple:
    """(total, active) parameter counts from init shapes (no allocation)."""
    from repro.models import encdec as ed_mod
    from repro.models import lm as lm_mod

    init = ed_mod.encdec_init if arch.is_encdec else lm_mod.lm_init
    shapes = jax.eval_shape(lambda: init(jax.random.PRNGKey(0), arch.config))
    total = emb = expert = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        names = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        n = 1
        for d in leaf.shape:
            n *= d
        total += n
        if names[-1] == "embedding":
            emb += n
        if names[-1] in ("w_gate", "w_up", "w_down"):
            expert += n
    # tied embeddings serve as the lm_head -> their matmul IS model compute
    tied = bool(getattr(arch.config, "tie_embeddings", False)) or arch.is_encdec
    active = total - (0 if tied else emb)
    cfg = arch.config
    moe = getattr(cfg, "moe", None)
    if moe is not None and expert:
        active -= expert * (1.0 - moe.top_k / moe.n_experts)
    return float(total), float(active)


def model_flops(arch, shape_name: str) -> float:
    sh = SHAPES[shape_name]
    total, active = _param_counts(arch)
    d_tokens = sh.global_batch * sh.seq_len
    if arch.is_encdec and sh.kind != "decode":
        d_tokens = sh.global_batch * (sh.seq_len + arch.config.enc_seq)
    if sh.kind == "train":
        return 6.0 * active * d_tokens
    if sh.kind == "prefill":
        return 2.0 * active * d_tokens
    return 2.0 * active * sh.global_batch  # decode: per emitted token


def run_lower(arch, shape_name, mesh, step_cfg, serve_dtype):
    """Lower one cell (train | prefill | decode) with explicit shardings."""
    from repro.runtime.train import (
        init_train_state,
        make_decode_step,
        make_prefill_step,
        make_train_step,
    )
    from repro.runtime.tree_sharding import batch_shardings, tree_shardings

    sh = SHAPES[shape_name]
    mode_quant = step_cfg.spring.is_quantized
    if sh.kind == "train":
        state_shapes = jax.eval_shape(
            lambda: init_train_state(jax.random.PRNGKey(0), arch, step_cfg)
        )
        batch_shapes = {
            k: v for k, v in arch.input_specs(shape_name, arch.config).items()
        }
        step = make_train_step(arch, step_cfg, mesh=mesh)
        state_sh = tree_shardings(state_shapes, mesh)
        batch_sh = batch_shardings(batch_shapes, mesh)
        return jax.jit(
            step,
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, None),
            donate_argnums=(0,),
        ).lower(state_shapes, batch_shapes)

    from repro.models import encdec as ed_mod
    from repro.models import lm as lm_mod

    init = ed_mod.encdec_init if arch.is_encdec else lm_mod.lm_init
    param_shapes = jax.eval_shape(lambda: init(jax.random.PRNGKey(0), arch.config))
    param_shapes = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, serve_dtype)
        if s.dtype == jnp.float32 else s, param_shapes)
    param_sh = tree_shardings(param_shapes, mesh)
    key_spec = jax.ShapeDtypeStruct((2,), jnp.uint32)
    if sh.kind == "prefill":
        batch_shapes = dict(arch.input_specs(shape_name, arch.config))
        batch_sh = batch_shardings(batch_shapes, mesh)
        fn = make_prefill_step(arch, step_cfg, mesh=mesh)
        out_shapes = jax.eval_shape(fn, param_shapes, batch_shapes, key_spec)
        out_sh = (None, tree_shardings(out_shapes[1], mesh))
        return jax.jit(
            fn, in_shardings=(param_sh, batch_sh, None), out_shardings=out_sh
        ).lower(param_shapes, batch_shapes, key_spec)

    # decode
    cache_shapes = arch.cache_specs(
        shape_name, arch.config,
        cache_dtype="int8" if step_cfg.int8_cache else None)
    cache_shapes = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, serve_dtype)
        if s.dtype == jnp.bfloat16 and mode_quant else s, cache_shapes)
    cache_sh = tree_shardings(cache_shapes, mesh)
    tok_shapes = dict(arch.input_specs(shape_name, arch.config))
    tok_sh = batch_shardings(tok_shapes, mesh)
    fn = make_decode_step(arch, step_cfg, mesh=mesh)
    return jax.jit(
        fn,
        in_shardings=(param_sh, tok_sh["tokens"], cache_sh, None),
        out_shardings=(None, cache_sh),
        donate_argnums=(2,),
    ).lower(param_shapes, tok_shapes["tokens"], cache_shapes, key_spec)


def _unrolled(arch):
    """Cost-shadow variant: fully unrolled layer scan so cost_analysis and
    the collective parse see every layer (XLA counts while bodies once)."""
    return dataclasses.replace(
        arch, config=dataclasses.replace(arch.config, scan_unroll=True)
    )


class DryrunSession(Session):
    """Multi-pod dry-run of one (arch x shape x mesh) cell: lower +
    compile + memory/cost/collective analyses, no allocation.

    NB: production meshes need host placeholder devices — run through
    ``repro.launch.dryrun`` (which sets ``XLA_FLAGS`` before jax loads)
    or export ``--xla_force_host_platform_device_count`` yourself.
    """

    run_mode = "dryrun"

    def _arch_for_lower(self):
        """ArchDef with the resolved concrete config swapped in —
        ``run_lower`` and the shape/cache spec helpers read
        ``arch.config``."""
        r = self.resolved
        cfg = r.config
        return dataclasses.replace(r.arch, config=cfg, reduced=lambda: cfg)

    def lower(self, mesh=None):
        """Resolve + build mesh + lower the cell (no compile): the cheap
        every-arch CI path ('dryrun-from-spec')."""
        spec = self.spec
        arch = self._arch_for_lower()
        if spec.shape.cell in arch.skipped_shapes():
            return None
        mesh = mesh or self.mesh or build_mesh(spec.shape.mesh)
        serve_dtype = (jnp.bfloat16 if spec.numerics.mode == "dense"
                       else jnp.float32)
        return run_lower(arch, spec.shape.cell, mesh, self.resolved.step,
                         serve_dtype)

    def run(self, verbose: bool = True) -> dict:
        with self.telemetry_scope():
            return self._run_body(verbose)

    def _run_body(self, verbose: bool = True) -> dict:
        from repro.kernels import registry as kernel_registry
        from repro.launch.hlo_analysis import (
            collective_bytes,
            fusion_adjusted_bytes,
            memory_summary,
            roofline_terms,
        )

        spec, r = self.spec, self.resolved
        arch = self._arch_for_lower()
        shape_name, mesh_spec, mode = (spec.shape.cell, spec.shape.mesh,
                                       spec.numerics.mode)
        sh = SHAPES[shape_name]
        step_cfg = r.step
        kpolicy = r.kernel_policy
        base = {
            "arch": spec.arch.id, "shape": shape_name,
            "mesh": mesh_spec.label(),
            "mode": mode, "variant": spec.dryrun.variant,
        }
        if shape_name in arch.skipped_shapes():
            return self._with_payload(dict(
                base, status="skipped",
                reason=arch.skipped_shapes()[shape_name]))
        mesh = self.mesh or build_mesh(mesh_spec)
        n_chips = mesh.devices.size
        serve_dtype = jnp.bfloat16 if mode == "dense" else jnp.float32

        kernel_registry.reset_dispatch_counts()
        from repro.runtime.sharding import mesh_fallback_counts

        fallbacks_before = mesh_fallback_counts()
        t0 = time.time()
        lowered = run_lower(arch, shape_name, mesh, step_cfg, serve_dtype)
        t_lower = time.time() - t0
        # what the program actually dispatched at trace time, plus what the
        # policy resolves for every registered op on this host
        kernel_dispatch = kernel_registry.dispatch_counts()
        kernel_impls = kernel_registry.resolution_table(kpolicy)

        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

        bf16c = (mode == "dense")  # TPU-native bf16; CPU legalized to f32
        cost = compiled.cost_analysis()
        mem = memory_summary(compiled.memory_analysis())
        hlo_text = compiled.as_text()
        coll = collective_bytes(hlo_text, bf16_correct=bf16c)
        adj = fusion_adjusted_bytes(hlo_text, bf16_correct=bf16c)["fusion_adjusted_bytes"]

        # Cost-shadow: recompile with the layer scan unrolled AND the
        # microbatch scan disabled so per-layer FLOPs/bytes/collectives
        # are all visible; memory comes from the real compile above.
        t_cost_compile = None
        if spec.dryrun.cost_unrolled:
            t0 = time.time()
            shadow_cfg = dataclasses.replace(step_cfg, microbatch=None)
            shadow = run_lower(_unrolled(arch), shape_name, mesh, shadow_cfg,
                               serve_dtype)
            shadow_c = shadow.compile()
            t_cost_compile = time.time() - t0
            cost = shadow_c.cost_analysis()
            shadow_text = shadow_c.as_text()
            coll = collective_bytes(shadow_text, bf16_correct=bf16c)
            adj = fusion_adjusted_bytes(
                shadow_text, bf16_correct=bf16c)["fusion_adjusted_bytes"]
            del shadow_c, shadow_text

        mf = model_flops(arch, shape_name)
        terms = roofline_terms(cost, coll["total"], n_chips, model_flops=mf,
                               adjusted_bytes=adj)

        result = dict(
            base,
            status="ok", n_chips=int(n_chips), microbatch=step_cfg.microbatch,
            lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
            cost_compile_s=round(t_cost_compile, 1) if t_cost_compile else None,
            kernel_policy=kpolicy.describe(),
            kernel_impls=kernel_impls,
            kernel_dispatch=kernel_dispatch,
            backward_sparsity=spec.sparsity.backward,
            memory=mem, collectives=coll, roofline=terms,
            mesh_fallbacks={
                logical: count - fallbacks_before.get(logical, 0)
                for logical, count in mesh_fallback_counts().items()
                if count - fallbacks_before.get(logical, 0)},
        )
        if n_chips > 1:
            # Measured packed-collective wire accounting at the probe
            # density (the lowered program never executes in a dry run;
            # this eager probe attributes inter-device traffic per cell).
            from repro.dist.collectives import collective_probe

            result["collective_probe"] = collective_probe(
                spec.sparsity.probe_density,
                world=max(2, min(4, int(n_chips))))
        if mode == "quant_sparse" and spec.sparsity.backward != "none" \
                and sh.kind == "train":
            # Measured fwd/bwd tile-skip at the probe density: the lowered
            # program never executes in a dry run, so this small eager
            # probe attributes backward sparsity savings per cell.
            from repro.kernels.masked_matmul.backward import sparsity_probe

            result["sparsity_probe"] = sparsity_probe(
                spec.sparsity.probe_density, size=256)
        if mode == "quant_sparse" and sh.kind == "decode":
            # Serving twin of the sparsity probe: measured KV wire bytes
            # of one packed block at the probe density.
            from repro.kernels.kv_cache.ops import kv_probe

            result["kv_probe"] = kv_probe(spec.sparsity.probe_density)
        result = self._with_payload(result)
        if verbose:
            print(json.dumps(result, indent=2))
            print(f"peak bytes/chip (arg+out+temp-alias): "
                  f"{mem['peak_bytes_per_chip_est']/1e9:.3f} GB",
                  file=sys.stderr)
        return result


SESSION_TYPES = {
    "train": TrainSession,
    "serve": ServeSession,
    "dryrun": DryrunSession,
}


def session_for(spec: RunSpec, **kw) -> Session:
    """The one dispatch point: a spec's ``run`` field picks its session."""
    return SESSION_TYPES[spec.run](spec, **kw)


# -- legacy kwargs -> spec bridges ------------------------------------------
# The pre-RunSpec launcher functions (train_loop / serve_session /
# run_cell) keep their exact signatures as wrappers over these.


def _call_overrides(pairs) -> list:
    return [(path, value, f"call:{path}") for path, value in pairs
            if value is not None]


def train_spec(arch_id: str = "llama3.2-1b", *, reduced: bool = True,
               steps: int = 100, batch: int = 8, seq: int = 128,
               mode: str = "dense", lr: float = 3e-3,
               fixed_point_weights: bool = False,
               kernel_impl: Optional[str] = None,
               backward_sparsity: str = "auto", stash: str = "none",
               ckpt_dir: Optional[str] = None, ckpt_every: int = 100,
               log_every: int = 10, seed: int = 0) -> RunSpec:
    """RunSpec equivalent of the legacy ``train_loop`` keyword surface."""
    return build_spec("train", overrides=_call_overrides([
        ("arch.id", arch_id), ("arch.reduced", reduced),
        ("train.steps", steps), ("shape.batch", batch), ("shape.seq", seq),
        ("numerics.mode", mode), ("optimizer.lr", lr),
        ("numerics.fixed_point_weights", fixed_point_weights),
        ("kernels.policy", kernel_impl),
        ("sparsity.backward", backward_sparsity),
        ("memstash.policy", stash),
        ("train.ckpt_dir", ckpt_dir or ""), ("train.ckpt_every", ckpt_every),
        ("train.log_every", log_every), ("seeds.seed", seed),
    ]))


def serve_spec(arch_id: str = "llama3.2-1b", *, reduced: bool = True,
               batch: int = 4, prompt_len: int = 32, gen: int = 16,
               mode: str = "dense", kernel_impl: Optional[str] = None,
               greedy: bool = True, seed: int = 0,
               slots: Optional[int] = None, queue: Optional[int] = None,
               static: bool = False, pages: bool = False,
               page_tokens: Optional[int] = None,
               num_pages: Optional[int] = None,
               overcommit: Optional[float] = None,
               prefix_cache: Optional[bool] = None,
               snapshot_every: Optional[int] = None,
               snapshot_path: Optional[str] = None,
               restore_path: Optional[str] = None,
               max_queue_depth: Optional[int] = None,
               deadline_ticks: Optional[int] = None,
               deadline_aware: Optional[bool] = None,
               priority_aware: Optional[bool] = None) -> RunSpec:
    """RunSpec equivalent of the legacy ``serve_session`` surface."""
    over = _call_overrides([
        ("arch.id", arch_id), ("arch.reduced", reduced),
        ("shape.batch", batch), ("shape.prompt_len", prompt_len),
        ("shape.gen", gen), ("numerics.mode", mode),
        ("kernels.policy", kernel_impl), ("serving.greedy", greedy),
        ("seeds.seed", seed), ("serving.static", static),
        ("serving.pages", pages),
    ])
    # slots/queue: None means "default to batch" and must stay None in the
    # spec (an explicit 0 must reach the engine's own validation)
    if slots is not None:
        over.append(("serving.slots", slots, "call:serving.slots"))
    if queue is not None:
        over.append(("serving.queue", queue, "call:serving.queue"))
    # paged-pool + spring-survive knobs: None keeps the spec default
    for key, value in (("page_tokens", page_tokens), ("num_pages", num_pages),
                       ("overcommit", overcommit),
                       ("prefix_cache", prefix_cache),
                       ("snapshot_every", snapshot_every),
                       ("snapshot_path", snapshot_path),
                       ("restore_path", restore_path),
                       ("max_queue_depth", max_queue_depth),
                       ("deadline_ticks", deadline_ticks),
                       ("deadline_aware", deadline_aware),
                       ("priority_aware", priority_aware)):
        if value is not None:
            over.append((f"serving.{key}", value, f"call:serving.{key}"))
    return build_spec("serve", overrides=over)


def dryrun_spec(arch_id: str, shape_name: str, mesh_kind: str = "single",
                mode: str = "dense", *, microbatch: Optional[int] = None,
                cost_unrolled: bool = True, seq_parallel: bool = False,
                bf16_logits: bool = False, layout: str = "tp",
                remat_policy: str = "full", cache_int8: bool = False,
                quant_opt: bool = False, variant: str = "baseline",
                kernel_impl: Optional[str] = None,
                backward_sparsity: str = "auto",
                probe_density: float = 0.5) -> RunSpec:
    """RunSpec equivalent of the legacy ``run_cell`` keyword surface
    (``arch.reduced`` stays null: dryrun resolves it to the full config)."""
    over = _call_overrides([
        ("arch.id", arch_id),
        ("shape.cell", shape_name), ("shape.mesh", mesh_kind),
        ("numerics.mode", mode),
        ("dryrun.cost_unrolled", cost_unrolled),
        ("shape.seq_parallel", seq_parallel),
        ("arch.bf16_logits", bf16_logits), ("shape.layout", layout),
        ("serving.int8_cache", cache_int8), ("dryrun.quant_opt", quant_opt),
        ("dryrun.variant", variant), ("kernels.policy", kernel_impl),
        ("sparsity.backward", backward_sparsity),
        ("sparsity.probe_density", probe_density),
    ])
    if microbatch is not None:
        over.append(("shape.microbatch", microbatch, "call:shape.microbatch"))
    # legacy quirk preserved: --remat-policy full was a no-op (the arch
    # keeps whatever remat_policy its config declares)
    if remat_policy != "full":
        over.append(("arch.remat_policy", remat_policy,
                     "call:arch.remat_policy"))
    return build_spec("dryrun", overrides=over)
