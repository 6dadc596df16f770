"""Training window: the program's jitted train step, driven for a fixed
number of seconds on batches drawn from the seed.

Set-up builds one object, the compiled step (``make_train_step``, jitted
with its state donated, as ``TrainSession`` does) and its state, with the
weights made on the device in one jitted call from the seed.  It drives
that object through its first ``check_steps`` steps on batches that all
differ; those steps compile the step and give the program's side of the
correctness comparison.  The window then goes on with the same object:
every step fetches its batch, runs, and ends in the loss read.

The traffic file gives ``batch``, ``seq``, the numerics ``mode`` and the
optimizer the job states.
"""

from __future__ import annotations

import math
import statistics
import time

import tracing
from harness import device_record, judge, program_config, seed_key


def batch_source(cell, seed: int):
    """The job's batches: ``(fn, key)`` with ``fn(key, step)`` the rows of
    ``step``, token ids drawn uniformly from the configuration's
    vocabulary (not its padding rows) from the seed, on the device."""
    import jax

    tr, vocab = cell.traffic, cell.config["vocab_size"]
    fn = jax.jit(lambda key, step: {"tokens": jax.random.randint(
        jax.random.fold_in(key, step), (tr["batch"], tr["seq"]), 0, vocab, dtype="int32")})
    return fn, jax.random.fold_in(seed_key(seed), 1)


def build(cell, seed: int, sets=()):
    """The program's step, its initial state and the batch source;
    ``sets`` are further settings of the program's run spec."""
    import jax

    from repro.api.spec import build_spec
    from repro.optim.optimizers import make_optimizer
    from repro.runtime.train import TrainState, make_train_step

    tr = cell.traffic
    ref = cell.reference()
    opt = tr["optimizer"]
    spec = build_spec("train", use_env=False, sets=[
        f"arch.id={cell.config['program']['arch']}",
        f"arch.reduced={str(cell.config['program'].get('preset') == 'reduced').lower()}",
        f"shape.batch={tr['batch']}", f"shape.seq={tr['seq']}",
        f"numerics.mode={tr['mode']}", "sparsity.backward=auto",
        f"optimizer.lr={opt['lr']}", f"optimizer.warmup_steps={opt['warmup_steps']}",
        *sets])
    r = spec.resolve()
    cfg = program_config(cell, r.config)
    view = r.arch.view(config=cfg)
    got = dict(beta1=r.step.optimizer.beta1, beta2=r.step.optimizer.beta2,
               eps=r.step.optimizer.eps, weight_decay=r.step.optimizer.weight_decay,
               grad_clip=r.step.optimizer.grad_clip, lr=r.step.optimizer.lr,
               warmup_steps=r.step.optimizer.warmup_steps)
    for k, v in got.items():
        if not math.isclose(v, opt[k]):
            raise RuntimeError(f"program optimizer {k}={v}, the job states {opt[k]}")
    opt_init, _ = make_optimizer(r.step.optimizer)
    key = seed_key(seed)

    def init(k):
        params = ref.init_params(cell.config, jax.random.fold_in(k, 0))
        return TrainState(params, opt_init(params), jax.numpy.zeros((), "int32"),
                          jax.random.fold_in(k, 2), None)

    state = jax.jit(init)(key)
    step = jax.jit(make_train_step(view, r.step), donate_argnums=(0,))
    batches, data_key = batch_source(cell, seed)
    return step, state, batches, data_key, r.step.optimizer


def program_readings(step, state, batches, data_key, opt, n: int, params_key, ref_init):
    """Drive the step through its first ``n`` steps; return the state and
    the program's losses, first-step gradient norms per leaf (the first
    moment after one step over 1 - beta1) and each leaf's change after
    step ``n``."""
    import jax
    import jax.numpy as jnp

    norms = jax.jit(lambda tree: [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                                  for x in jax.tree_util.tree_leaves(tree)])
    change = jax.jit(lambda params, k: [
        jnp.sqrt(jnp.sum(jnp.square(p - q)))
        for p, q in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(ref_init(k)))])
    names = [_path(p) for p, _ in jax.tree_util.tree_flatten_with_path(state.params)[0]]
    losses, grads = [], None
    for i in range(n):
        state, metrics = step(state, batches(data_key, i))
        losses.append(float(metrics["loss"]))
        if i == 0:
            grads = [float(x) / (1.0 - opt.beta1) for x in norms(state.opt_state.m)]
    moved = [float(x) for x in change(state.params, params_key)]
    return state, {"losses": losses, "grad_norms": dict(zip(names, grads)),
                   "change_norms": dict(zip(names, moved))}


def _path(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def compare(prog: dict, ref: dict) -> dict:
    """The numbers that decide ``correct``.

    loss_gap     largest |program - reference| of the per-step losses
    grad_gap     worst leaf's |program - reference| first-step gradient
                 norm, over the larger of that leaf's reference norm and
                 the median leaf's
    change_gap   the same for each leaf's change after the last step,
                 over the leaves whose reference gradient is at least a
                 thousandth of the median leaf's (the others move under
                 Adam by round-off alone)
    """
    return {k: v for k, v in compare_leaves(prog, ref).items() if not k.endswith("_leaf")}


def compare_leaves(prog: dict, ref: dict) -> dict:
    """``compare``'s numbers, with the leaf that sets each gap of norms."""
    loss_gap = max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"]))

    def worst(p, r, names):
        med = statistics.median([r[k] for k in names])
        return max((abs(p[k] - r[k]) / max(r[k], med, 1e-30), k) for k in names)

    raw = ref["raw_grad_norms"]
    med_raw = statistics.median(raw.values())
    moving = [k for k in raw if raw[k] >= 1e-3 * med_raw]
    grad, grad_leaf = worst(prog["grad_norms"], ref["grad_norms"], list(raw))
    change, change_leaf = worst(prog["change_norms"], ref["change_norms"], moving)
    return {"loss_gap": loss_gap, "grad_gap": grad, "change_gap": change,
            "grad_leaf": grad_leaf, "change_leaf": change_leaf}


def run(cell, seed: int, seconds: float, trace: bool, clock, devices) -> dict:
    import gc

    import jax

    tr = cell.traffic
    n_check = tr["check_steps"]
    ref = cell.reference()
    step, state, batches, data_key, opt = build(cell, seed)
    params_key = jax.random.fold_in(seed_key(seed), 0)
    state, prog = program_readings(
        step, state, batches, data_key, opt, n_check, params_key,
        lambda k: ref.init_params(cell.config, k))
    jax.block_until_ready(batches(data_key, n_check))  # compile the fetch
    compiles_before = clock.compiles

    # -- the measured window ---------------------------------------------
    profiled = tracing.Profiled() if trace else None
    steps, failed, host_s = 0, 0, 0.0
    i = n_check
    if profiled:
        profiled.__enter__()
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.monotonic()
        while True:
            h0 = time.monotonic()
            with jax.profiler.TraceAnnotation("bench.data"):
                batch = batches(data_key, i)
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                state, metrics = step(state, batch)
            host_s += time.monotonic() - h0
            with jax.profiler.TraceAnnotation("bench.loss_read"):
                loss = float(metrics["loss"])
            steps += 1
            i += 1
            failed += not math.isfinite(loss)
            t1 = time.monotonic()
            if t1 - t0 >= seconds:
                break
    if profiled:
        profiled.__exit__(None, None, None)
    window_s = t1 - t0
    tokens = steps * tr["batch"] * tr["seq"]
    record = device_record(devices)
    compiled_in_window = clock.compiles - compiles_before

    # -- correctness, after the window and with the program's state freed --
    del state, metrics, batch, step
    gc.collect()
    ref_batches = [jax.device_get(batches(data_key, j)["tokens"]) for j in range(n_check)]
    readings = ref.train_readings(cell.config, params_key, ref_batches, tr["optimizer"])
    numbers = compare(prog, readings)
    ok, rows = judge(numbers, cell.checks.get("limits", {}))
    return {
        "window_start": t0,
        "end_to_end": {"train_tokens_per_s": tokens / window_s},
        "correct": ok and failed == 0,
        "attempted": steps, "failed": failed, "checks": rows,
        "device": record, "trace": profiled.trace if profiled else None,
        "counters": {"steps": steps, "tokens": tokens, "window_s": window_s,
                     "host_s_per_step": host_s / steps,
                     "compiles_in_window": compiled_in_window,
                     "batch": tr["batch"], "seq": tr["seq"]},
        "spans": [],
        "detail": {},
        "extra": {"program": prog, "reference": readings},
    }

