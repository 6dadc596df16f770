"""RunSpec: one declarative, serializable configuration API for every
execution mode (train / serve / dryrun).

SPRING's contribution is a *single* accelerator serving training and
inference from the same sparsity/precision machinery; the repo mirrors
that with a single spec.  A :class:`RunSpec` is a frozen tree of small
frozen sections (arch + shape + numerics/SR + sparsity fwd/bwd +
memstash + kernel policy + serving/scheduler + seeds), built by layered
resolution

    defaults -> ArchDef -> spec file (JSON/TOML) -> SPRING_* env -> CLI

with per-field provenance, and resolved by :meth:`RunSpec.resolve` into
the concrete objects the step builders consume today
(``configs.base.ResolvedArch``, ``SpringConfig``, ``StepConfig``,
``KernelPolicy``, ``MemstashConfig``).  The ArchDef layer is
value-conditional: fields left at ``"auto"`` (today: ``memstash.policy``)
are resolved against the architecture's family at ``resolve()`` time, so
a spec file round-trips bit-identically no matter which arch it names.

Canonical form: ``to_json()`` (sorted keys) is the reproducibility
artifact every launcher embeds in its output — dryrun JSON, benchmark
``--json``, ``results/serving/*.json`` — and ``spec_hash()`` ties a
result row to the exact configuration that produced it.

Unknown fields are rejected with did-you-mean suggestions; every choice
field validates against the same constant the subsystem itself uses
(``STASH_POLICIES``, ``BACKWARD_SPARSITY_CHOICES``, ``SHAPES``, ...), so
the spec cannot drift from the machinery it configures.
"""

import dataclasses
import difflib
import hashlib
import json
import logging
import os
from typing import Mapping, Optional, Sequence

from repro.core.fixedpoint import SPRING_FORMAT
from repro.core.spring_ops import BACKWARD_SPARSITY_CHOICES, MODES
from repro.kernels.registry import KernelPolicy
from repro.memstash.config import STASH_POLICIES, MemstashConfig
from repro.optim.optimizers import OptimizerConfig
from repro.runtime.train import StepConfig

RUN_MODES = ("train", "serve", "dryrun")
MESH_KINDS = ("single", "multi", "debug", "debug_multi")
LAYOUTS = ("tp", "fsdp")

#: Compat spellings: the flat pre-mesh path ``shape.mesh=<kind>`` (old
#: JSON artifacts, ``--set shape.mesh=multi``, the dryrun ``--mesh``
#: shim) lands on the nested ``shape.mesh.kind`` leaf.  Aliases are
#: resolved in ``_Builder.set`` so every layer (file/env/CLI/kwargs)
#: gets them for free.
_ALIASES = {"shape.mesh": "shape.mesh.kind"}

#: Environment layer: SPRING_<NAME> -> dotted RunSpec field.  Applied
#: between the spec file and CLI overrides.  ``SPRING_SET`` additionally
#: accepts ';'-separated ``key=value`` dotted overrides.
ENV_FIELDS = {
    "SPRING_ARCH": "arch.id",
    "SPRING_MODE": "numerics.mode",
    "SPRING_KERNEL_IMPL": "kernels.policy",
    "SPRING_BACKWARD_SPARSITY": "sparsity.backward",
    "SPRING_STASH": "memstash.policy",
    "SPRING_SEED": "seeds.seed",
}
#: ``SPRING_SET="k=v;k=v"`` dotted overrides.  Entries are separated by
#: ";" (not ","), so comma-bearing values — the KernelPolicy grammar
#: ``kernels.policy=ref,ssd_scan=jnp`` — stay representable.
ENV_SET = "SPRING_SET"

# Dry-run gradient-accumulation defaults (moved here from launch/dryrun:
# the resolver is the one source of truth for spec -> StepConfig).
DEFAULT_TRAIN_MICROBATCH = 8  # grad accumulation: activation memory / 8
# MoE dispatch buffers replicate tokens x top_k; VLM carries 26B params:
# these archs need deeper accumulation to fit 16 GB/chip.
TRAIN_MICROBATCH_OVERRIDES = {
    "olmoe-1b-7b": 16, "deepseek-v2-lite-16b": 16, "internvl2-26b": 16,
}

# FSDP logical-rule overrides (pure DP x FSDP: batch over all mesh axes).
FSDP_RULES = (
    ("batch", (("pod", "data", "model"), ("data", "model"))),
    ("heads", (None,)), ("kv_heads", (None,)),
    ("mlp_act", (None,)), ("vocab_act", (None,)),
    ("w_qkv", (None,)), ("w_mlp", (None,)), ("w_vocab", (None,)),
    ("w_embed", (("data", "model"), ("data",))),
    ("cache_batch", (("pod", "data", "model"), ("data", "model"), ("data",))),
    ("cache_seq", (None,)),
)
SEQ_PARALLEL_RULES = (("seq", (("model",), None)),)


class SpecError(ValueError):
    """A RunSpec could not be built or validated."""


# ---------------------------------------------------------------------------
# Sections.  Every field is JSON-primitive so the spec serializes without
# custom encoders; "auto" marks arch/mode-conditional resolution.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ArchSection:
    """Which architecture, at which size, with arch-config overrides."""

    id: str = "llama3.2-1b"
    # None = run-conditional default, resolved like memstash "auto":
    # train/serve use the reduced smoke config, dryrun analyzes the
    # published full config (its whole point).
    reduced: Optional[bool] = None
    remat_policy: str = ""  # "" = arch default; full | block_io | stash
    bf16_logits: bool = False


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Device-mesh topology (spring-mesh, DESIGN.md §14).

    Explicit axis extents take precedence: when ``pod*data*model > 1``,
    sessions build a ``("pod", "data", "model")`` mesh of exactly that
    shape from the available devices.  Otherwise ``kind`` picks one of
    the named launch meshes (``single`` = no mesh).  ``data`` must be a
    power of two when > 1: the packed-collective bit-exactness guarantee
    (tree-reduce of replicated gradients, then exact /2^k rescale) only
    holds for power-of-two world sizes.
    """

    kind: str = "single"  # named mesh when no explicit axes are set
    pod: int = 1
    data: int = 1
    model: int = 1

    @property
    def explicit(self) -> bool:
        return self.pod * self.data * self.model > 1

    def label(self) -> str:
        """Flat string for run artifacts (roofline rows key on it)."""
        if self.explicit:
            return f"pod{self.pod}.data{self.data}.model{self.model}"
        return self.kind


@dataclasses.dataclass(frozen=True)
class ShapeSection:
    """Problem shape: train batch/seq, serve prompt/gen, dryrun cell/mesh."""

    batch: int = 8
    seq: int = 128
    prompt_len: int = 32
    gen: int = 16
    cell: str = "train_4k"  # dryrun shape-cell name (configs.SHAPES)
    mesh: MeshSpec = MeshSpec()  # device topology (kind or explicit axes)
    microbatch: Optional[int] = None  # None = per-arch dryrun default
    layout: str = "tp"
    seq_parallel: bool = False


@dataclasses.dataclass(frozen=True)
class NumericsSection:
    """SPRING numerics: mode, rounding, fixed-point master weights."""

    mode: str = "dense"  # dense | quant | quant_sparse
    stochastic: str = "auto"  # auto (train: SR, serve: nearest) | on | off
    operand_rounding: str = "stochastic"  # stochastic | nearest
    weights_pre_quantized: bool = False
    fixed_point_weights: bool = False  # SR Q4.16 master weights


@dataclasses.dataclass(frozen=True)
class SparsitySection:
    """Backward-direction sparsity (the forward mask is numerics.mode)."""

    backward: str = "auto"  # none | auto; a lowering is pinned in kernels.policy
    probe_density: float = 0.5  # dryrun sparsity/kv probe density


@dataclasses.dataclass(frozen=True)
class MemstashSection:
    """Compressed activation stash policy (DESIGN.md §4.3)."""

    policy: str = "auto"  # auto (family default) | none | remat | stash
    value_bits: int = 20
    capacity: float = 1.0
    min_elems: int = 1024


@dataclasses.dataclass(frozen=True)
class KernelsSection:
    """Kernel-dispatch policy string (KernelPolicy.parse grammar)."""

    policy: str = "auto"  # e.g. "ref" | "ssd_scan=jnp" | "ref,ssd_scan=jnp"


@dataclasses.dataclass(frozen=True)
class OptimizerSection:
    """Train/dryrun optimizer (serving uses no optimizer)."""

    kind: str = "adamw"  # adamw | sgdm
    lr: float = 3e-3
    warmup_steps: int = 10


@dataclasses.dataclass(frozen=True)
class TrainSection:
    """Training-session driver knobs."""

    steps: int = 100
    ckpt_dir: str = ""  # "" = no checkpointing
    ckpt_every: int = 100
    log_every: int = 10


@dataclasses.dataclass(frozen=True)
class ServingSection:
    """Continuous-batching engine shape + scheduler."""

    slots: Optional[int] = None  # None = shape.batch
    queue: Optional[int] = None  # None = shape.batch
    greedy: bool = True
    static: bool = False  # force the pre-engine static reference path
    int8_cache: bool = False
    # spring-pages (DESIGN.md §12): paged COW KV pool
    pages: bool = False  # serve on the paged pool instead of slot-monolithic
    page_tokens: int = 8  # cache rows per page frame
    num_pages: Optional[int] = None  # physical page budget; None = dense-equiv
    overcommit: float = 1.5  # logical frames / physical pages
    prefix_cache: bool = True  # chain-hash prefix sharing (COW)
    # spring-survive (DESIGN.md §13): elastic serving under failure/overload
    snapshot_every: int = 0  # save an engine snapshot every N ticks (0 = off)
    snapshot_path: str = ""  # "" = spring_snapshot.npz when snapshots are on
    restore_path: str = ""  # restore + drain a saved snapshot, skip new work
    max_queue_depth: Optional[int] = None  # shed "queue_full" past this depth
    deadline_ticks: Optional[int] = None  # shed "deadline" if queued longer
    deadline_aware: bool = False  # EDF admission instead of strict FCFS
    priority_aware: bool = False  # admit higher Request.priority first


@dataclasses.dataclass(frozen=True)
class DryrunSection:
    """Dry-run analysis options."""

    cost_unrolled: bool = True
    quant_opt: bool = False  # pre-quantized weights + nearest operands
    variant: str = "baseline"


@dataclasses.dataclass(frozen=True)
class SeedsSection:
    """One master seed: params, data stream, request keys derive from it."""

    seed: int = 0


@dataclasses.dataclass(frozen=True)
class TelemetrySection:
    """spring-trace: span tracing + metrics export (DESIGN.md §11).

    Off by default — the disabled path is a no-op and runs carry no
    telemetry payload.  Enabling must never change computed values, only
    add measurement (sealed by the parity test in test_telemetry.py).
    """

    enabled: bool = False
    #: Chrome trace-event JSON output ("" = derive from train.out_dir /
    #: the --json artifact stem; load in Perfetto / chrome://tracing)
    trace_path: str = ""
    #: fraction of root spans recorded, deterministic accumulator (no
    #: PRNG); nested spans inherit the root's decision
    sample_rate: float = 1.0


_SECTIONS = {
    "arch": ArchSection,
    "shape": ShapeSection,
    "numerics": NumericsSection,
    "sparsity": SparsitySection,
    "memstash": MemstashSection,
    "kernels": KernelsSection,
    "optimizer": OptimizerSection,
    "train": TrainSection,
    "serving": ServingSection,
    "dryrun": DryrunSection,
    "seeds": SeedsSection,
    "telemetry": TelemetrySection,
}

_CHOICES = {
    "run": RUN_MODES,
    "numerics.mode": tuple(MODES),
    "numerics.stochastic": ("auto", "on", "off"),
    "numerics.operand_rounding": ("stochastic", "nearest"),
    "sparsity.backward": BACKWARD_SPARSITY_CHOICES,
    "memstash.policy": ("auto",) + STASH_POLICIES,
    "arch.remat_policy": ("", "full", "block_io", "stash"),
    "shape.mesh.kind": MESH_KINDS,
    "shape.layout": LAYOUTS,
    "optimizer.kind": ("adamw", "sgdm"),
}


def field_paths() -> dict:
    """{dotted path: python type} for every RunSpec field.  Nested
    dataclass fields (``shape.mesh``) contribute their leaves plus the
    compat alias path (typed ``str``) so legacy flat spellings keep
    validating."""
    idx = {"run": str}
    for sec, cls in _SECTIONS.items():
        for f in dataclasses.fields(cls):
            if dataclasses.is_dataclass(f.type):
                for sf in dataclasses.fields(f.type):
                    idx[f"{sec}.{f.name}.{sf.name}"] = sf.type
            else:
                idx[f"{sec}.{f.name}"] = f.type
    for alias in _ALIASES:
        idx[alias] = str
    return idx


_FIELDS = None


def _fields() -> dict:
    global _FIELDS
    if _FIELDS is None:
        _FIELDS = field_paths()
    return _FIELDS


def _suggest(key: str, candidates) -> str:
    close = difflib.get_close_matches(str(key), [str(c) for c in candidates],
                                      n=3, cutoff=0.4)
    return f" — did you mean {', '.join(repr(m) for m in close)}?" if close else ""


def _coerce_str(path: str, raw: str):
    """Coerce a CLI/env string to the field's declared type."""
    typ = _fields()[path]
    s = raw.strip()
    low = s.lower()
    if typ in (Optional[int], Optional[bool]):
        if low in ("none", "null", ""):
            return None
        typ = int if typ == Optional[int] else bool
    if typ is bool:
        if low in ("true", "1", "yes", "on"):
            return True
        if low in ("false", "0", "no", "off"):
            return False
        raise SpecError(f"{path}: expected a boolean, got {raw!r}")
    try:
        if typ is int:
            return int(s)
        if typ is float:
            return float(s)
    except ValueError as e:
        raise SpecError(f"{path}: {e}") from None
    return s


def _check_typed(path: str, value):
    """Validate/normalize an already-typed value (JSON layer, kwargs)."""
    typ = _fields()[path]
    if typ in (Optional[int], Optional[bool]):
        if value is None:
            return None
        typ = int if typ == Optional[int] else bool
    if typ is bool:
        if not isinstance(value, bool):
            raise SpecError(f"{path}: expected a boolean, got {value!r}")
        return value
    if typ is int:
        if isinstance(value, bool) or not isinstance(value, int):
            raise SpecError(f"{path}: expected an integer, got {value!r}")
        return value
    if typ is float:
        if isinstance(value, bool) or not isinstance(value, (int, float)):
            raise SpecError(f"{path}: expected a number, got {value!r}")
        return float(value)
    if not isinstance(value, str):
        raise SpecError(f"{path}: expected a string, got {value!r}")
    return value


# ---------------------------------------------------------------------------
# RunSpec.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RunSpec:
    """The one declarative run configuration.  Frozen; equality ignores
    ``provenance`` (metadata about *where* each field came from, recorded
    by the layered builder and rendered into run artifacts)."""

    run: str = "train"
    arch: ArchSection = ArchSection()
    shape: ShapeSection = ShapeSection()
    numerics: NumericsSection = NumericsSection()
    sparsity: SparsitySection = SparsitySection()
    memstash: MemstashSection = MemstashSection()
    kernels: KernelsSection = KernelsSection()
    optimizer: OptimizerSection = OptimizerSection()
    train: TrainSection = TrainSection()
    serving: ServingSection = ServingSection()
    dryrun: DryrunSection = DryrunSection()
    seeds: SeedsSection = SeedsSection()
    telemetry: TelemetrySection = TelemetrySection()
    provenance: Mapping[str, str] = dataclasses.field(
        default_factory=dict, compare=False, repr=False)

    # -- serialization ------------------------------------------------------

    def to_dict(self) -> dict:
        d = {"run": self.run}
        for name in _SECTIONS:
            d[name] = dataclasses.asdict(getattr(self, name))
        return d

    def to_json(self, indent: Optional[int] = 2) -> str:
        """Canonical JSON: sorted keys, stable across dict ordering."""
        return json.dumps(self.to_dict(), sort_keys=True, indent=indent) + "\n"

    def spec_hash(self) -> str:
        """Hash of the canonical compact JSON (ties artifacts to configs)."""
        compact = json.dumps(self.to_dict(), sort_keys=True,
                             separators=(",", ":"))
        return hashlib.sha256(compact.encode()).hexdigest()[:16]

    def state_hash(self) -> str:
        """``spec_hash`` with the restart-operational serving fields
        (snapshot cadence/paths) *and* the mesh topology neutralized —
        the stamp embedded in serving snapshots (DESIGN.md §13).  A run
        that merely *restores* an artifact necessarily differs from the
        run that wrote it in exactly these fields — and a snapshot taken
        on one device count must restore onto another (elastic rescale
        across topologies, DESIGN.md §14) — so they must not poison the
        compatibility check; anything numerics/shape/arch-shaped still
        rejects."""
        d = self.to_dict()
        for field in ("snapshot_every", "snapshot_path", "restore_path"):
            d["serving"][field] = ServingSection.__dataclass_fields__[
                field].default
        d["shape"]["mesh"] = dataclasses.asdict(MeshSpec())
        compact = json.dumps(d, sort_keys=True, separators=(",", ":"))
        return hashlib.sha256(compact.encode()).hexdigest()[:16]

    def payload(self) -> dict:
        """The reproducibility block every run artifact embeds."""
        return {
            "spec": self.to_dict(),
            "spec_hash": self.spec_hash(),
            "provenance": dict(self.provenance),
        }

    @classmethod
    def from_dict(cls, data: dict, label: str = "dict") -> "RunSpec":
        return build_spec(data=data, data_label=label, use_env=False)

    @classmethod
    def from_json(cls, text: str, label: str = "json") -> "RunSpec":
        try:
            data = json.loads(text)
        except json.JSONDecodeError as e:
            raise SpecError(f"invalid spec JSON: {e}") from None
        return cls.from_dict(data, label=label)

    @classmethod
    def from_file(cls, path: str) -> "RunSpec":
        return build_spec(spec_file=path, use_env=False)

    def _get(self, path: str):
        """Walk a dotted field path of any depth."""
        obj = self
        for part in path.split("."):
            obj = getattr(obj, part)
        return obj

    def describe(self) -> str:
        """Flat field = value  [provenance] rendering (debug/--explain)."""
        prov = dict(self.provenance)
        lines = []
        for path in sorted(_fields()):
            if path in _ALIASES:  # alias leaves are rendered, not the alias
                continue
            value = self._get(path)
            lines.append(f"{path} = {value!r}  [{prov.get(path, 'default')}]")
        return "\n".join(lines)

    # -- validation ---------------------------------------------------------

    def validate(self) -> "RunSpec":
        for path, choices in _CHOICES.items():
            value = self._get(path)
            if value not in choices:
                raise SpecError(
                    f"{path}: unknown value {value!r}; choose from "
                    f"{choices}{_suggest(str(value), choices)}")
        from repro.configs import SHAPES
        if self.shape.cell not in SHAPES:
            raise SpecError(
                f"shape.cell: unknown shape {self.shape.cell!r}; choose "
                f"from {tuple(SHAPES)}"
                f"{_suggest(self.shape.cell, SHAPES)}")
        if not 0.0 <= self.sparsity.probe_density <= 1.0:
            raise SpecError("sparsity.probe_density must be in [0, 1]")
        for ax in ("pod", "data", "model"):
            if getattr(self.shape.mesh, ax) < 1:
                raise SpecError(f"shape.mesh.{ax} must be >= 1")
        if self.shape.mesh.data > 1 and \
                self.shape.mesh.data & (self.shape.mesh.data - 1):
            raise SpecError(
                "shape.mesh.data must be a power of two: the packed "
                "collective bit-exactness seal (pairwise tree-reduce + "
                "exact /2^k rescale) only holds for power-of-two worlds")
        if not 0.0 < self.telemetry.sample_rate <= 1.0:
            raise SpecError("telemetry.sample_rate must be in (0, 1]")
        if self.serving.page_tokens < 1:
            raise SpecError("serving.page_tokens must be >= 1")
        if self.serving.overcommit < 1.0:
            raise SpecError("serving.overcommit must be >= 1.0")
        if self.serving.num_pages is not None and self.serving.num_pages < 1:
            raise SpecError("serving.num_pages must be >= 1 (or null)")
        if self.serving.snapshot_every < 0:
            raise SpecError("serving.snapshot_every must be >= 0")
        if (self.serving.max_queue_depth is not None
                and self.serving.max_queue_depth < 1):
            raise SpecError("serving.max_queue_depth must be >= 1 (or null)")
        if (self.serving.deadline_ticks is not None
                and self.serving.deadline_ticks < 0):
            raise SpecError("serving.deadline_ticks must be >= 0 (or null)")
        if self.serving.restore_path and self.serving.snapshot_every:
            # one engine either resumes an artifact or produces them; both
            # at once would overwrite the artifact being drained
            if (self.serving.snapshot_path or "spring_snapshot.npz") == \
                    self.serving.restore_path:
                raise SpecError(
                    "serving.restore_path equals the snapshot output path; "
                    "set serving.snapshot_path to a different file")
        try:
            KernelPolicy.parse(self._kernel_spec())
        except ValueError as e:
            raise SpecError(f"kernels.policy: {e}") from None
        try:
            MemstashConfig(
                policy="none" if self.memstash.policy == "auto"
                else self.memstash.policy,
                value_bits=self.memstash.value_bits,
                capacity=self.memstash.capacity,
                min_elems=self.memstash.min_elems)
        except ValueError as e:
            raise SpecError(f"memstash: {e}") from None
        return self

    # -- resolution ---------------------------------------------------------

    def _kernel_spec(self) -> str:
        return "" if self.kernels.policy in ("", "auto") else self.kernels.policy

    def resolved_memstash_policy(self, family: str) -> str:
        """The ArchDef layer: ``"auto"`` dispatches on the workload family
        through :func:`repro.configs.base.default_memstash` — the single
        source of truth for the per-family recommendation."""
        if self.memstash.policy != "auto":
            return self.memstash.policy
        from repro.configs.base import default_memstash

        return default_memstash(family).policy

    def resolve(self) -> "ResolvedRun":
        """Produce the concrete config objects today's step builders eat."""
        self.validate()
        from repro.configs import SHAPES, get_arch

        try:
            arch = get_arch(self.arch.id)
        except KeyError as e:
            raise SpecError(str(e)) from None
        # reduced=None: run-conditional (train/serve smoke-size, dryrun
        # analyzes the published config) — same for CLI and API callers
        use_reduced = (self.run != "dryrun" if self.arch.reduced is None
                       else self.arch.reduced)
        cfg = arch.reduced() if use_reduced else arch.config
        cfg = dataclasses.replace(cfg)  # defensive copy
        if self.arch.remat_policy and hasattr(cfg, "remat_policy"):
            cfg = dataclasses.replace(cfg, remat_policy=self.arch.remat_policy)
        if self.arch.bf16_logits and hasattr(cfg, "bf16_logits"):
            cfg = dataclasses.replace(cfg, bf16_logits=True)

        ms_policy = self.resolved_memstash_policy(arch.family)
        memstash = MemstashConfig(
            policy=ms_policy, value_bits=self.memstash.value_bits,
            capacity=self.memstash.capacity, min_elems=self.memstash.min_elems)
        # An *explicitly requested* stash/remat policy re-routes the LM
        # residual checkpoints (train_loop's --stash semantics); the
        # family-dispatched "auto" recommendation only configures the
        # stash points the model already has.
        if (self.run == "train" and self.memstash.policy != "auto"
                and ms_policy != "none"):
            if not hasattr(cfg, "remat_policy"):
                logging.getLogger("repro.api").warning(
                    "memstash.policy=%s has no residual-checkpoint effect "
                    "for %s (config has no remat_policy)",
                    ms_policy, self.arch.id)
            elif ms_policy == "stash":
                cfg = dataclasses.replace(cfg, remat_policy="stash")
            else:  # "remat": force plain recompute even if the reduced
                # variant disabled remat
                cfg = dataclasses.replace(cfg, remat=True, remat_policy="full")

        kernel_policy = KernelPolicy.parse(self._kernel_spec())
        stochastic = {"on": True, "off": False}.get(
            self.numerics.stochastic, self.run != "serve")
        spring = dataclasses.replace(
            MODES[self.numerics.mode], stochastic=stochastic,
            kernels=kernel_policy, backward_sparsity=self.sparsity.backward)
        if spring.is_quantized:
            spring = dataclasses.replace(
                spring,
                weights_pre_quantized=self.numerics.weights_pre_quantized
                or (self.run == "dryrun" and self.dryrun.quant_opt),
                operand_rounding="nearest"
                if (self.run == "dryrun" and self.dryrun.quant_opt)
                else self.numerics.operand_rounding)

        if self.run == "serve":
            # serving: no optimizer in the program; nearest rounding keeps
            # a request's tokens a function of the request alone
            step = StepConfig(spring=spring, optimizer=OptimizerConfig(),
                              int8_cache=self.serving.int8_cache)
        else:
            # Dryrun lowers the optimizer *kind* only: lr/warmup are
            # training-session knobs with no bearing on the analyses, and
            # keeping them out preserves bit-parity with every pre-RunSpec
            # dryrun artifact (legacy run_cell: OptimizerConfig(kind=...)).
            opt = (OptimizerConfig(kind=self.optimizer.kind)
                   if self.run == "dryrun" else OptimizerConfig(
                       kind=self.optimizer.kind, lr=self.optimizer.lr,
                       warmup_steps=self.optimizer.warmup_steps,
                       weight_format=SPRING_FORMAT
                       if self.numerics.fixed_point_weights else None))
            if self.run == "train":
                step = StepConfig(spring=spring, memstash=memstash, optimizer=opt,
                                  microbatch=self.shape.microbatch)
            else:  # dryrun
                microbatch = self.shape.microbatch
                if microbatch is None and SHAPES[self.shape.cell].kind == "train":
                    microbatch = TRAIN_MICROBATCH_OVERRIDES.get(
                        self.arch.id, DEFAULT_TRAIN_MICROBATCH)
                rules = ()
                if self.shape.seq_parallel:
                    rules += SEQ_PARALLEL_RULES
                if self.shape.layout == "fsdp":
                    rules += FSDP_RULES
                step = StepConfig(
                    spring=spring, optimizer=opt, microbatch=microbatch,
                    rules_override=rules, int8_cache=self.serving.int8_cache)

        return ResolvedRun(
            spec=self, arch=arch, view=arch.view(config=cfg), config=cfg,
            spring=spring, step=step, kernel_policy=kernel_policy,
            memstash=memstash, memstash_policy=ms_policy)


@dataclasses.dataclass(frozen=True)
class ResolvedRun:
    """What ``RunSpec.resolve()`` hands the sessions: the exact objects
    the pre-RunSpec launchers used to assemble by hand."""

    spec: RunSpec
    arch: object  # configs.base.ArchDef
    view: object  # configs.base.ResolvedArch (concrete config picked)
    config: object  # the model config (LMConfig | EncDecConfig)
    spring: object  # SpringConfig
    step: StepConfig
    kernel_policy: KernelPolicy
    memstash: MemstashConfig
    memstash_policy: str  # family-dispatched policy actually in force


# ---------------------------------------------------------------------------
# Layered builder.
# ---------------------------------------------------------------------------


class _Builder:
    def __init__(self):
        self._values: dict = {}
        self._prov: dict = {}

    def set(self, path: str, value, label: str, from_str: bool = False):
        path = _ALIASES.get(path, path)
        if path not in _fields():
            raise SpecError(
                f"unknown RunSpec field {path!r} (from {label})"
                f"{_suggest(path, _fields())}")
        self._values[path] = (_coerce_str(path, value) if from_str
                              else _check_typed(path, value))
        self._prov[path] = label

    def overlay_nested(self, data: dict, label: str):
        if not isinstance(data, dict):
            raise SpecError(f"spec root must be an object (from {label})")
        for key, value in data.items():
            if key == "run":
                self.set("run", value, label)
                continue
            if key not in _SECTIONS:
                raise SpecError(
                    f"unknown RunSpec section {key!r} (from {label})"
                    f"{_suggest(key, list(_SECTIONS) + ['run'])}")
            if not isinstance(value, dict):
                raise SpecError(
                    f"section {key!r} must be an object (from {label})")
            for leaf, v in value.items():
                if isinstance(v, dict):  # nested subsection (shape.mesh)
                    for subleaf, sv in v.items():
                        self.set(f"{key}.{leaf}.{subleaf}", sv, label)
                else:
                    self.set(f"{key}.{leaf}", v, label)

    def overlay_env(self, environ: Mapping[str, str]):
        for var, path in ENV_FIELDS.items():
            if var in environ and environ[var] != "":
                self.set(path, environ[var], f"env:{var}", from_str=True)
        for token in (t for t in environ.get(ENV_SET, "").split(";") if t.strip()):
            path, eq, value = token.partition("=")
            if not eq:
                raise SpecError(
                    f"{ENV_SET} entries must be ';'-separated key=value "
                    f"pairs, got {token!r}")
            self.set(path.strip(), value, f"env:{ENV_SET}", from_str=True)

    def overlay_sets(self, sets: Sequence[str], label: str = "set"):
        for item in sets:
            path, eq, value = item.partition("=")
            if not eq:
                raise SpecError(f"--set expects key=value, got {item!r}")
            self.set(path.strip(), value, f"{label}:{path.strip()}",
                     from_str=True)

    def build(self) -> RunSpec:
        sections = {}
        for name, cls in _SECTIONS.items():
            kw = {}
            for f in dataclasses.fields(cls):
                path = f"{name}.{f.name}"
                if dataclasses.is_dataclass(f.type):
                    sub = {sf.name: self._values[f"{path}.{sf.name}"]
                           for sf in dataclasses.fields(f.type)
                           if f"{path}.{sf.name}" in self._values}
                    if sub:
                        kw[f.name] = f.type(**sub)
                elif path in self._values:
                    kw[f.name] = self._values[path]
            try:
                sections[name] = cls(**kw)
            except ValueError as e:
                raise SpecError(f"{name}: {e}") from None
        prov = {p: "default" for p in _fields()}
        prov.update(self._prov)
        spec = RunSpec(run=self._values.get("run", "train"),
                       provenance=prov, **sections)
        return spec.validate()


def load_spec_data(path: str) -> dict:
    """Read a spec file; format from extension (.json, .toml)."""
    if path.endswith(".toml"):
        try:
            import tomllib  # py3.11+
        except ModuleNotFoundError:
            try:
                import tomli as tomllib  # type: ignore[no-redef]
            except ModuleNotFoundError:
                raise SpecError(
                    f"cannot read {path}: TOML support needs python >= 3.11 "
                    "(tomllib) or the 'tomli' package; use JSON instead"
                ) from None
        with open(path, "rb") as f:
            try:
                return tomllib.load(f)
            except tomllib.TOMLDecodeError as e:
                raise SpecError(f"invalid TOML in {path}: {e}") from None
    with open(path) as f:
        try:
            return json.load(f)
        except json.JSONDecodeError as e:
            raise SpecError(f"invalid JSON in {path}: {e}") from None


def build_spec(
    run: Optional[str] = None,
    *,
    spec_file: Optional[str] = None,
    data: Optional[dict] = None,
    data_label: str = "data",
    overrides: Sequence[tuple] = (),  # (path, typed value, label)
    sets: Sequence[str] = (),  # "key=value" strings (CLI --set)
    use_env: bool = True,
    environ: Optional[Mapping[str, str]] = None,
) -> RunSpec:
    """Assemble a RunSpec through the documented layer order:

      defaults -> data (caller base layer, e.g. an example preset)
               -> [ArchDef at resolve()] -> spec file -> SPRING_* env
               -> overrides (legacy flags / call kwargs) -> launcher run
               -> --set

    ``overrides`` carry their own labels (``legacy:--stash``,
    ``call:stash``) so provenance distinguishes shimmed spellings from
    native ones.
    """
    b = _Builder()
    if data is not None:
        b.overlay_nested(data, data_label)
    if spec_file is not None:
        b.overlay_nested(load_spec_data(spec_file), f"file:{spec_file}")
    if use_env:
        b.overlay_env(os.environ if environ is None else environ)
    for path, value, label in overrides:
        b.set(path, value, label)
    if run is not None:
        b.set("run", run, "launcher")
    b.overlay_sets(sets)
    return b.build()
