"""Training launcher: a thin adapter over the RunSpec API.

The native surface is a spec file plus dotted overrides:

  PYTHONPATH=src python -m repro.launch.train --spec examples/specs/train_quant_sparse.json
  PYTHONPATH=src python -m repro.launch.train --set arch.id=llama3.2-1b \
      --set train.steps=300 --set shape.batch=8 --set shape.seq=128

Every pre-redesign flag (``--arch``, ``--steps``, ``--stash``,
``--kernel-impl``, ``--backward-sparsity``, ...) still works as a
deprecated shim that resolves to the same RunSpec field (see
``repro.api.cli``).  ``--explain`` prints each field with the layer that
set it; ``--json`` writes the result with the canonical resolved spec so
the run is reproducible from one artifact.

``train_loop`` keeps its historical keyword signature as a wrapper over
``TrainSession`` for programmatic callers (tests, examples, benches).
"""

from __future__ import annotations

import json
import logging
import math
import sys

from repro.api.cli import flag, make_parser, run_main
from repro.api.sessions import TrainSession, train_spec
from repro.core.spring_ops import MODES  # re-export (legacy import site)
from repro.runtime.compile_cache import enable_compile_cache

log = logging.getLogger("repro.train")

#: Legacy flag spellings -> RunSpec fields (all warn with the --set form).
LEGACY_FLAGS = (
    flag("--arch", "arch.id"),
    flag("--reduced", "arch.reduced", const=True),
    flag("--steps", "train.steps", type=int),
    flag("--batch", "shape.batch", type=int),
    flag("--seq", "shape.seq", type=int),
    flag("--mode", "numerics.mode", choices=list(MODES)),
    flag("--lr", "optimizer.lr", type=float),
    flag("--fixed-point-weights", "numerics.fixed_point_weights", const=True),
    flag("--kernel-impl", "kernels.policy"),
    flag("--backward-sparsity", "sparsity.backward",
         choices=["none", "auto"]),
    flag("--stash", "memstash.policy", choices=["none", "remat", "stash"]),
    flag("--ckpt-dir", "train.ckpt_dir"),
    flag("--ckpt-every", "train.ckpt_every", type=int),
)


def train_loop(
    arch_id: str = "llama3.2-1b",
    *,
    reduced: bool = True,
    steps: int = 100,
    batch: int = 8,
    seq: int = 128,
    mode: str = "dense",
    lr: float = 3e-3,
    fixed_point_weights: bool = False,
    kernel_impl: str | None = None,  # KernelPolicy spec, e.g. "ref" | "ssd_scan=jnp"
    backward_sparsity: str = "auto",  # none | auto
    stash: str = "none",  # memstash policy: none | remat | stash
    ckpt_dir: str | None = None,
    ckpt_every: int = 100,
    log_every: int = 10,
    mesh=None,
    seed: int = 0,
) -> dict:
    """Legacy keyword surface: builds the equivalent RunSpec and runs a
    :class:`repro.api.TrainSession`."""
    spec = train_spec(
        arch_id, reduced=reduced, steps=steps, batch=batch, seq=seq,
        mode=mode, lr=lr, fixed_point_weights=fixed_point_weights,
        kernel_impl=kernel_impl, backward_sparsity=backward_sparsity,
        stash=stash, ckpt_dir=ckpt_dir, ckpt_every=ckpt_every,
        log_every=log_every, seed=seed)
    return TrainSession(spec, mesh=mesh).run()


def build_parser():
    return make_parser(__doc__, LEGACY_FLAGS, json_out=True)


def main(argv=None) -> int:
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(name)s %(message)s")
    args = build_parser().parse_args(argv)
    spec = run_main("train", args, LEGACY_FLAGS)
    enable_compile_cache()
    out = TrainSession(spec).run()
    print(f"loss {out['first_loss']:.4f} -> {out['last_loss']:.4f} "
          f"({spec.train.steps} steps, slow={out['slow_steps']}) "
          f"[spec {out['spec_hash']}]")
    if args.json:
        payload = {k: v for k, v in out.items() if k != "state"}
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, default=float)
    if not all(math.isfinite(v) for v in out["losses"]):
        print("error: non-finite training loss", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
