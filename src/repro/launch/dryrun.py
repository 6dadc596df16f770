"""Multi-pod dry-run launcher: a thin adapter over the RunSpec API.

THE FIRST TWO LINES must run before any other import (jax locks the
device count on first init): they give this process 512 placeholder host
devices so ``jax.make_mesh`` can build the production meshes.

Per cell this emits: memory_analysis (fits-on-chip proof), cost_analysis
(FLOPs/bytes for §Roofline), the parsed collective-bytes table, and the
canonical resolved RunSpec (+hash/provenance), as JSON consumed by
EXPERIMENTS.md and ``roofline_report``.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --spec examples/specs/dryrun_decode_debug.json
  PYTHONPATH=src python -m repro.launch.dryrun --set arch.id=qwen2-7b \
      --set shape.cell=train_4k --set shape.mesh=multi --out results/q.json

Legacy flag spellings (``--arch``, ``--shape``, ``--kernel-impl``, ...)
shim to the same RunSpec fields with a DeprecationWarning; ``run_cell``
keeps its keyword signature for programmatic callers.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("REPRO_DRYRUN_XLA_FLAGS")
    or "--xla_force_host_platform_device_count=512"
)

import argparse  # noqa: E402,F401  (re-export site for older callers)
import json  # noqa: E402
import sys  # noqa: E402

from repro.api.cli import _SKIP, flag, make_parser, spec_from_args  # noqa: E402
from repro.api.sessions import (  # noqa: E402
    DryrunSession,
    build_mesh,
    dryrun_spec,
    model_flops,
    run_lower,
)
from repro.api.spec import (  # noqa: E402
    DEFAULT_TRAIN_MICROBATCH,
    TRAIN_MICROBATCH_OVERRIDES,
)
from repro.configs import SHAPES  # noqa: E402
from repro.core.spring_ops import MODES  # noqa: E402

LEGACY_FLAGS = (
    flag("--arch", "arch.id"),
    flag("--shape", "shape.cell", choices=list(SHAPES)),
    flag("--mesh", "shape.mesh",
         choices=["single", "multi", "debug", "debug_multi"]),
    flag("--mode", "numerics.mode", choices=list(MODES)),
    flag("--microbatch", "shape.microbatch", type=int),
    flag("--no-unrolled-cost", "dryrun.cost_unrolled", const=False),
    flag("--seq-parallel", "shape.seq_parallel", const=True),
    flag("--bf16-logits", "arch.bf16_logits", const=True),
    flag("--layout", "shape.layout", choices=["tp", "fsdp"]),
    # legacy quirk preserved: --remat-policy full was a no-op
    flag("--remat-policy", "arch.remat_policy",
         choices=["full", "block_io"],
         transform=lambda v: _SKIP if v == "full" else v),
    flag("--cache-int8", "serving.int8_cache", const=True),
    flag("--quant-opt", "dryrun.quant_opt", const=True),
    flag("--variant", "dryrun.variant"),
    flag("--kernel-impl", "kernels.policy"),
    flag("--backward-sparsity", "sparsity.backward",
         choices=["none", "auto"]),
    flag("--probe-density", "sparsity.probe_density", type=float),
)


def run_cell(arch_id: str, shape_name: str, mesh_kind: str, mode: str,
             microbatch=None, verbose: bool = True, cost_unrolled: bool = True,
             seq_parallel: bool = False, bf16_logits: bool = False,
             layout: str = "tp", remat_policy: str = "full",
             cache_int8: bool = False, quant_opt: bool = False,
             variant: str = "baseline", kernel_impl: str | None = None,
             backward_sparsity: str = "auto",
             probe_density: float = 0.5) -> dict:
    """Legacy keyword surface: builds the equivalent RunSpec and runs a
    :class:`repro.api.DryrunSession` (full configs, like the old path)."""
    spec = dryrun_spec(
        arch_id, shape_name, mesh_kind, mode, microbatch=microbatch,
        cost_unrolled=cost_unrolled, seq_parallel=seq_parallel,
        bf16_logits=bf16_logits, layout=layout, remat_policy=remat_policy,
        cache_int8=cache_int8, quant_opt=quant_opt, variant=variant,
        kernel_impl=kernel_impl, backward_sparsity=backward_sparsity,
        probe_density=probe_density)
    return DryrunSession(spec).run(verbose=verbose)


def build_parser():
    ap = make_parser(__doc__, LEGACY_FLAGS, out=True)
    return ap


def main(argv=None):
    ap = build_parser()
    args = ap.parse_args(argv)
    try:
        spec = spec_from_args("dryrun", args, LEGACY_FLAGS)
    except Exception as e:  # SpecError -> argparse-style exit
        raise SystemExit(f"error: {e}") from None
    # the pre-RunSpec CLI required --arch/--shape; keep a bare invocation
    # from silently compiling the default cell on the production mesh
    # (arch.reduced=null resolves run-conditionally: dryrun = full config)
    for path, old_flag in (("arch.id", "--arch"), ("shape.cell", "--shape")):
        if spec.provenance.get(path, "default") == "default":
            ap.error(f"{path} must be set (--spec file, --set {path}=..., "
                     f"or the deprecated {old_flag})")
    if args.explain:
        print(spec.describe())
        return 0
    result = DryrunSession(spec).run(verbose=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2)
    return 0 if result["status"] in ("ok", "skipped") else 1


if __name__ == "__main__":
    sys.exit(main())
