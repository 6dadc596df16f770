#!/usr/bin/env python3
"""Smoke test of SPRING's train and serve paths on a TPU, at full width.

Run from the repository root on a machine with a TPU:

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the sharded (spring-mesh) path

One chip runs three phases through the normal entry points
(``repro.api.TrainSession`` / ``ServeSession``):

  train   mamba2-780m at its published size (48 layers, d_model 1536),
          quant_sparse with the sparsity-aware backward, a few AdamW steps;
  serve   llama3.2-1b at its published size (16 layers, d_model 2048,
          vocab 128256) in quant_sparse and in dense: 8 requests over 4
          slots, so requests join mid-flight;
  kernels each main-path op's ``pallas`` lowering against its ``ref`` at
          the shapes those phases use, under the registry's own compare
          spec.

It fails (non-zero exit, no result line) when the first device is not a
TPU, when a loss or logit is non-finite, when a kernel disagrees with its
reference, or when a main-path op that registers a ``pallas`` lowering
was dispatched to anything else.

``--chips 4`` runs only the sharded path and what it is compared with:
``shape.mesh.data=4`` training and serving against the same runs on one
device, and reports whether losses and tokens are bit-identical.

Weights are random, drawn from ``--seed``.  The last line of standard
output is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

#: ops on the train/serve path; each registers a ``pallas`` lowering
MAIN_PATH_OPS = ("masked_matmul", "masked_matmul_dx", "masked_matmul_dw",
                 "stochastic_round", "kv_pack", "mask_pack", "ssd_scan")

TRAIN_ARCH, TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = "mamba2-780m", 1, 1024, 4
SERVE_ARCH, SLOTS, REQUESTS, PROMPT, GEN = "llama3.2-1b", 4, 8, 128, 32


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit is counted with its retrieval time)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def split(self) -> dict:
        return {"compile_s": self.seconds, "cache_hits": self.hits,
                "cache_misses": self.misses}


def peak_gb() -> float:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) / 1e9


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def all_finite(values) -> bool:
    return all(math.isfinite(v) for v in values)


def build(run: str, sets: list[str], seed: int):
    from repro.api.spec import build_spec

    return build_spec(run, use_env=False, sets=sets + [f"seeds.seed={seed}"])


# -- one chip ----------------------------------------------------------------


def train_phase(seed: int, clock: CompileClock) -> dict:
    from repro.api.sessions import TrainSession

    spec = build("train", [
        f"arch.id={TRAIN_ARCH}", "arch.reduced=false",
        f"shape.batch={TRAIN_BATCH}", f"shape.seq={TRAIN_SEQ}",
        f"train.steps={TRAIN_STEPS}", "numerics.mode=quant_sparse",
        "sparsity.backward=auto", "train.log_every=1",
    ], seed)
    c0 = clock.seconds
    out = TrainSession(spec).run()
    out.pop("state")
    losses, step_s = out["losses"], out["step_s"]
    log(f"train {TRAIN_ARCH} quant_sparse batch {TRAIN_BATCH} x seq "
        f"{TRAIN_SEQ}: losses {losses}")
    log(f"train step seconds {step_s} (first includes compile), "
        f"compile {clock.seconds - c0:.3f}s, peak {peak_gb():.3f} GB")
    check(len(losses) == TRAIN_STEPS and all_finite(losses),
          f"train losses not finite: {losses}")
    return {"losses": losses, "step_s": step_s}


def serve_phase(mode: str, params, seed: int, clock: CompileClock) -> dict:
    from repro.api.sessions import ServeSession

    spec = build("serve", [
        f"arch.id={SERVE_ARCH}", "arch.reduced=false",
        f"shape.batch={SLOTS}", f"serving.slots={SLOTS}",
        f"serving.queue={REQUESTS}", f"shape.prompt_len={PROMPT}",
        f"shape.gen={GEN}", f"numerics.mode={mode}",
    ], seed)
    c0 = clock.seconds
    out = ServeSession(spec, params=params).run()
    toks = [t for req in out["per_request"] for t in req["tokens"]]
    vocab = spec.resolve().config.vocab
    log(f"serve {SERVE_ARCH} {mode}: {len(out['per_request'])} requests over "
        f"{out['slots']} slots, {len(toks)} tokens, "
        f"{out['decode_steps']} decode ticks, "
        f"prefill {out['prefill_s']:.3f}s, decode {out['decode_s']:.3f}s, "
        f"mean occupancy {out['mean_occupancy']:.3f}")
    la = out["latency"]
    log(f"serve {mode} tick seconds p50 {la['token_s']['p50']:.4f} "
        f"p99 {la['token_s']['p99']:.4f}, ttft p50 {la['ttft_s']['p50']:.3f}, "
        f"compile {clock.seconds - c0:.3f}s, peak {peak_gb():.3f} GB")
    log(f"serve {mode} request 0 tokens {out['per_request'][0]['tokens']}")
    check(out["finite"], f"serve {mode}: non-finite logits")
    check(len(out["per_request"]) == REQUESTS
          and all(len(r["tokens"]) == GEN for r in out["per_request"]),
          f"serve {mode}: expected {REQUESTS} requests of {GEN} tokens")
    check(all(0 <= t < vocab for t in toks), f"serve {mode}: token out of vocab")
    return {"tokens": len(toks), "decode_s": out["decode_s"]}


def _grid_operand(key, shape, bits: int, zero_tiles: bool):
    """Values on the SPRING fixed-point grid (integers scaled by 2**-bits),
    half of them zero, with whole 128x128 tiles pruned so the kernel's
    tile skip fires.  Small integers keep every product and partial sum
    exact in float32, so the exact compare holds in any summation order,
    as in the registry's own examples."""
    import jax
    import jax.numpy as jnp

    v = jnp.round(jax.random.normal(key, shape) * 64) / 2.0**bits
    keep = jax.random.uniform(jax.random.fold_in(key, 1), shape) > 0.5
    v = v * keep
    if zero_tiles:
        v = v.at[:128, :128].set(0.0).at[-128:, 128:256].set(0.0)
    return v


def kernel_cases(seed: int) -> list:
    """(op, args, kwargs, vmapped) at the shapes the phases above use."""
    import jax
    import jax.numpy as jnp

    from repro.configs.registry import get_arch
    from repro.models.lm import lm_init_cache

    key = jax.random.PRNGKey(seed)
    k = [jax.random.fold_in(key, i) for i in range(12)]
    train_cfg = get_arch(TRAIN_ARCH).config
    serve_cfg = get_arch(SERVE_ARCH).config
    ssm = train_cfg.ssm
    # the mamba2 in_proj on one training batch: (tokens, d) @ (d, proj)
    tokens, d = TRAIN_BATCH * TRAIN_SEQ, train_cfg.d_model
    proj = 2 * ssm.d_inner + 2 * ssm.n_groups * ssm.d_state + ssm.n_heads
    x = _grid_operand(k[0], (tokens, d), 8, True)
    w = _grid_operand(k[1], (d, proj), 9, True)
    g = _grid_operand(k[2], (tokens, proj), 9, True)
    # the llama gate projection on one prefill
    xl = _grid_operand(k[3], (PROMPT, serve_cfg.d_model), 8, True)
    wl = _grid_operand(k[4], (serve_cfg.d_model, serve_cfg.d_ff), 9, True)
    acc = jax.random.normal(k[5], (tokens, proj)) * 3

    # one llama K leaf of the serving pool, blocks (layer x slot) of
    # (max_len x kv_heads x head_dim) with the unfilled tail zero
    max_len = PROMPT + GEN + 1
    cache = jax.eval_shape(lambda: lm_init_cache(serve_cfg, SLOTS, max_len))
    kshape = cache["unit_0"]["k"].shape  # (layers, slots, max_len, kv, hd)
    block = math.prod(kshape[2:])
    filled = jnp.arange(max_len)[:, None] < PROMPT + GEN // 2
    kv = jax.random.normal(k[6], (math.prod(kshape[:2]), max_len,
                                  block // max_len))
    kv = jnp.where(filled[None], kv, 0.0).astype(jnp.bfloat16).reshape(-1, block)

    # the SSD scan of one training batch
    h, p, n, ng = ssm.n_heads, ssm.head_dim, ssm.d_state, ssm.n_groups
    xs = jax.random.normal(k[7], (TRAIN_BATCH, TRAIN_SEQ, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[8], (TRAIN_BATCH, TRAIN_SEQ, h)) - 2)
    a = -jnp.exp(jax.random.normal(k[9], (h,)) * 0.5)
    bm = jax.random.normal(k[10], (TRAIN_BATCH, TRAIN_SEQ, ng, n)) / n**0.5
    cm = jax.random.normal(k[11], (TRAIN_BATCH, TRAIN_SEQ, ng, n)) / n**0.5
    return [
        ("masked_matmul", (x, w, jnp.uint32(seed + 1)), {}, False),
        ("masked_matmul", (xl, wl, jnp.uint32(seed + 2)), {}, False),
        ("masked_matmul_dx", (g, w), {}, False),
        ("masked_matmul_dw", (x, g), {}, False),
        ("stochastic_round", (acc, jnp.uint32(seed + 3)), {}, False),
        ("kv_pack", (kv,), {}, True),
        ("mask_pack", (acc,), {}, False),
        ("ssd_scan", (xs, dt, a, bm, cm), {}, False),
    ]


def kernel_phase(seed: int, clock: CompileClock) -> list:
    import jax

    from repro.kernels import registry

    rows = []
    c0 = clock.seconds
    # fp32 products on both sides: the references are fp32 oracles
    with jax.default_matmul_precision("float32"):
        for op, args, kwargs, vmapped in kernel_cases(seed):
            impls, spec = registry.impls(op), registry.op_spec(op)
            run = {}
            for name in ("pallas", spec.oracle):
                fn = impls[name].fn
                if vmapped:
                    fn = jax.vmap(fn)
                t0 = time.monotonic()
                run[name] = jax.block_until_ready(fn(*args, **kwargs))
                run[name + "_s"] = time.monotonic() - t0
            shapes = [tuple(getattr(a, "shape", ())) for a in args]
            try:
                dev = registry.compare_outputs(op, run["pallas"], run[spec.oracle])
            except AssertionError as e:
                raise SmokeFailure(f"kernel {op} {shapes}: {e}") from None
            rows.append({"op": op, "shapes": shapes,
                         "compare": spec.compare_spec(), "deviation": dev})
            log(f"kernel {op} {shapes}: pallas agrees with {spec.oracle} "
                f"({spec.compare_spec()['kind']}, deviation {dev:.3g}), "
                f"first call {run['pallas_s']:.3f}s")
    log(f"kernel check compile {clock.seconds - c0:.3f}s")
    return rows


def check_dispatch(counts: dict, expected: tuple) -> None:
    from repro.kernels import registry

    for op, by_impl in sorted(counts.items()):
        if "pallas" in registry.impls(op) and set(by_impl) != {"pallas"}:
            raise SmokeFailure(
                f"{op} dispatched to {by_impl} though a pallas lowering is "
                "registered")
    missing = [op for op in expected if op not in counts]
    check(not missing, f"main-path ops never dispatched: {missing}")


def one_chip(seed: int, clock: CompileClock) -> dict:
    import jax

    from repro.configs.registry import get_arch
    from repro.kernels import registry
    from repro.models.lm import lm_init

    table = registry.resolution_table()
    log("resolution table: " + json.dumps(table, sort_keys=True))
    bad = {op: table[op] for op in MAIN_PATH_OPS if table[op] != "pallas"}
    check(not bad, f"main-path ops that do not resolve to pallas: {bad}")

    registry.reset_dispatch_counts()
    train = train_phase(seed, clock)
    params = lm_init(jax.random.PRNGKey(seed), get_arch(SERVE_ARCH).config)
    serve = {mode: serve_phase(mode, params, seed, clock)
             for mode in ("quant_sparse", "dense")}
    del params
    counts = registry.dispatch_counts()
    log("dispatch counts: " + json.dumps(counts, sort_keys=True))
    check_dispatch(counts, ("masked_matmul", "masked_matmul_dx",
                            "masked_matmul_dw", "kv_pack", "ssd_scan"))
    kernels = kernel_phase(seed, clock)
    return {"train": train, "serve": serve, "kernels": kernels}


# -- four chips --------------------------------------------------------------


def four_chips(seed: int, clock: CompileClock) -> dict:
    """spring-mesh (DESIGN.md §14): the data-sharded step against the
    same run on one device.  Training replicates params, optimizer state
    and the packed gradient exchange's all-gathered buffers on every
    device, so it runs the reduced llama3.2-1b; serving shards request
    rows and runs llama3.2-1b at its published size."""
    from repro.api.sessions import ServeSession, TrainSession

    out = {}
    for mode in ("dense", "quant_sparse"):
        sets = ["arch.id=llama3.2-1b", "arch.reduced=true", "train.steps=3",
                "shape.batch=8", "shape.seq=128", f"numerics.mode={mode}"]
        runs = {}
        for label, extra in (("one_device", []),
                             ("data4", ["shape.mesh.data=4"])):
            c0 = clock.seconds
            r = TrainSession(build("train", sets + extra, seed)).run()
            runs[label] = r["losses"]
            log(f"mesh train {mode} {label} ({r['mesh']}): losses "
                f"{r['losses']}, step seconds {r['step_s']}, "
                f"compile {clock.seconds - c0:.3f}s")
            check(all_finite(r["losses"]), f"mesh train {mode} {label}: "
                  "non-finite loss")
        same = runs["one_device"] == runs["data4"]
        log(f"mesh train {mode}: sharded losses bit-identical to one "
            f"device: {same}")
        out[f"train_{mode}_identical"] = same

        sets = ["arch.id=llama3.2-1b", "arch.reduced=false",
                "serving.static=true", "shape.batch=4",
                f"shape.prompt_len={PROMPT}", f"shape.gen={GEN}",
                f"numerics.mode={mode}"]
        toks = {}
        for label, extra in (("one_device", []),
                             ("data4", ["shape.mesh.data=4"])):
            c0 = clock.seconds
            r = ServeSession(build("serve", sets + extra, seed)).run()
            toks[label] = [[int(t) for t in row] for row in r["generated"]]
            log(f"mesh serve {mode} {label} ({r['mesh']}): decode "
                f"{r['decode_s']:.3f}s, row 0 tokens {toks[label][0]}, "
                f"compile {clock.seconds - c0:.3f}s")
            check(r["finite"], f"mesh serve {mode} {label}: non-finite logits")
        same = toks["one_device"] == toks["data4"]
        agree = sum(a == b for ra, rb in zip(toks["one_device"], toks["data4"])
                    for a, b in zip(ra, rb))
        log(f"mesh serve {mode}: sharded tokens bit-identical to one device: "
            f"{same} ({agree}/{4 * GEN} positions agree)")
        out[f"serve_{mode}_identical"] = same
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="", help="also write the results here "
                    "as JSON")
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no SPRING sources under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} visible",
              file=sys.stderr)
        return 2

    from repro.runtime.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {devices[0].device_kind} x {len(devices)}, jax "
        f"{jax.__version__}")
    clock = CompileClock()
    t0 = time.monotonic()
    try:
        results = (four_chips if args.chips == 4 else one_chip)(args.seed, clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    results["wall_s"] = time.monotonic() - t0
    results.update(clock.split())
    log(f"total compile {clock.seconds:.3f}s (persistent cache hits "
        f"{clock.hits}, misses {clock.misses}), wall "
        f"{results['wall_s']:.1f}s, peak {peak_gb():.3f} GB")
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(results, indent=1, default=str))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
