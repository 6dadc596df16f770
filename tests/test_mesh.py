"""spring-mesh suite: packed-collective bit-identity, wire accounting,
MeshSpec threading, divisibility-fallback telemetry, and — on an 8-device
host (CI mesh job) — the single-device-oracle parity seals for sharded
training and serving (DESIGN.md §14).

Simulation-mode tests run everywhere (tier-1); tests taking the
``debug_mesh`` fixture self-skip unless
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` was exported
before jax initialized.
"""

from __future__ import annotations

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.api.spec import RunSpec, SpecError, build_spec
from repro.dist import collectives as C
from repro.kernels import registry
from repro.memstash.format import formula_bits_per_elem

pytestmark = pytest.mark.mesh

registry.ensure_registered()

# stacked (D, n) payloads shaped like the three numerics modes' wires
PAYLOADS = {
    "dense": C._shard_block(0, 4, 1024, 1.0),
    "quant": C._shard_block(1, 4, 512, 0.5, jnp.bfloat16),
    "quant_sparse": C._shard_block(2, 4, 500, 0.1),
}


# -- packed collectives, simulation mode (tier-1) ----------------------------


@pytest.mark.parametrize("mode", sorted(PAYLOADS))
@pytest.mark.parametrize("impl", ["ref", "jnp", "interpret"])
def test_packed_matches_dense_per_shard(mode, impl):
    """The packed wire format is bit-invisible: every impl's all-gather /
    reduce-scatter equals the dense reference exactly, per shard."""
    x = PAYLOADS[mode]
    ag = registry.resolve("packed_all_gather", impl).fn(x)
    assert jnp.array_equal(ag, C.dense_all_gather(x))
    rs = registry.resolve("packed_reduce_scatter", impl).fn(x)
    assert jnp.array_equal(rs, C.dense_reduce_scatter(x))


def test_tree_sum_identical_addends_exact():
    """The bit-exactness seal: a power-of-two pairwise tree over D
    identical addends is exactly D*g, and /D recovers g bit-for-bit."""
    g = jax.random.normal(jax.random.PRNGKey(3), (4096,))
    rows = jnp.stack([g, g, g, g])
    total = C._tree_sum(rows)
    assert jnp.array_equal(total, g * 4.0)
    assert jnp.array_equal(total / 4, g)


def test_tree_sum_rejects_non_power_of_two():
    with pytest.raises(ValueError, match="power of two"):
        C._tree_sum(jnp.ones((3, 8)))


def test_wire_bits_obey_formula():
    """collective_wire_bits == length*(20*density + 1) per device at word
    alignment — the paper's interface formula, single-sourced with
    memstash."""
    world, length = 4, 1 << 12  # word-aligned
    x = C._shard_block(5, world, length, 0.37)
    nnz = int(jnp.count_nonzero(x))
    measured = C.collective_wire_bits(nnz, length, world)
    formula = world * length * formula_bits_per_elem(
        nnz / (world * length), C.COLLECTIVE_VALUE_BITS)
    assert measured == pytest.approx(formula)
    probe = C.collective_probe(0.5, world=2, length=1 << 12)
    assert probe["wire_vs_formula"] == pytest.approx(1.0)
    assert probe["exact"]
    assert probe["compression_vs_fp32"] > 2.0


def test_collective_probe_emits_telemetry():
    from repro.telemetry.metrics import default_registry

    default_registry().reset()
    C.collective_probe(0.5, world=2)
    snap = default_registry().snapshot()
    fam = snap["spring_mesh_collective_bytes_total"]
    kinds = {c["labels"]["kind"] for c in fam["cells"]}
    assert "packed_all_gather" in kinds
    assert all(c["value"] > 0 for c in fam["cells"])
    assert "spring_mesh_collective_density" in snap


# -- MeshSpec threading through RunSpec (tier-1) -----------------------------


def test_meshspec_fields_and_alias():
    spec = build_spec("train", use_env=False, sets=["shape.mesh.data=4"])
    assert spec.shape.mesh.data == 4
    assert spec.shape.mesh.explicit
    assert spec.shape.mesh.label() == "pod1.data4.model1"
    assert spec.provenance["shape.mesh.data"].startswith("set")
    # legacy string spelling routes through the alias to the kind field
    old = build_spec("train", use_env=False, sets=["shape.mesh=debug"])
    assert old.shape.mesh.kind == "debug"
    assert not old.shape.mesh.explicit
    assert old.shape.mesh.label() == "debug"


def test_meshspec_roundtrip_and_legacy_dict():
    spec = build_spec("train", use_env=False, sets=["shape.mesh.data=2"])
    again = RunSpec.from_dict(spec.to_dict())
    assert again == spec
    # pre-mesh artifacts carried a plain string: the alias absorbs it
    d = spec.to_dict()
    d["shape"]["mesh"] = "single"
    legacy = RunSpec.from_dict(d)
    assert legacy.shape.mesh.kind == "single"
    assert not legacy.shape.mesh.explicit


def test_meshspec_validation():
    with pytest.raises(SpecError, match="power of two"):
        build_spec("train", use_env=False, sets=["shape.mesh.data=3"])
    with pytest.raises(SpecError, match=">= 1"):
        build_spec("train", use_env=False, sets=["shape.mesh.model=0"])
    with pytest.raises(SpecError, match="shape.mesh.kind"):
        build_spec("train", use_env=False, sets=["shape.mesh=bogus"])


# -- divisibility fallback telemetry (satellite) -----------------------------


def test_fallback_counter_on_indivisible_axis():
    """A rule that wants to shard but cannot divide replicates AND
    counts — the previously-silent tree_sharding fallback."""
    from repro.runtime.sharding import logical_to_spec, mesh_fallback_counts
    from repro.telemetry.metrics import default_registry

    default_registry().reset()
    stub = types.SimpleNamespace(shape={"data": 3})
    spec = logical_to_spec(("batch",), (4,), stub)  # 4 % 3 != 0
    assert spec == P(None)
    assert mesh_fallback_counts() == {"batch": 1}
    # divisible dims shard without counting
    assert logical_to_spec(("batch",), (6,), stub) == P("data")
    assert mesh_fallback_counts() == {"batch": 1}


# -- sharded-vs-oracle parity seals (CI mesh job, 8 host devices) ------------


TRAIN_SETS = ["arch.id=llama3.2-1b", "train.steps=2", "shape.batch=4",
              "shape.seq=16"]
SERVE_SETS = ["arch.id=llama3.2-1b", "shape.batch=4", "shape.prompt_len=8",
              "shape.gen=3", "serving.static=true"]


def test_axis_mode_matches_simulation(debug_mesh):
    """The real wire hop: shard_map'd collectives over the data axis
    reproduce simulation mode bit-for-bit."""
    x = C._shard_block(6, 4, 512, 0.4)
    flat = x.reshape(-1)  # P("data") slices back to the stacked rows

    ag = jax.shard_map(lambda v: C.packed_all_gather(v, axis_name="data"),
                       mesh=debug_mesh, in_specs=P("data"), out_specs=P(),
                       axis_names={"data"}, check_vma=False)
    assert jnp.array_equal(jax.jit(ag)(flat), C.packed_all_gather(x))

    rs = jax.shard_map(lambda v: C.packed_reduce_scatter(v, axis_name="data"),
                       mesh=debug_mesh, in_specs=P("data"),
                       out_specs=P("data"), axis_names={"data"}, check_vma=False)
    assert jnp.array_equal(jax.jit(rs)(flat),
                           C.packed_reduce_scatter(x).reshape(-1))


def test_sharded_train_losses_match_oracle(debug_mesh):
    from repro.api.sessions import TrainSession

    oracle = TrainSession(
        build_spec("train", use_env=False, sets=TRAIN_SETS)).run()
    sharded = TrainSession(
        build_spec("train", use_env=False,
                   sets=TRAIN_SETS + ["shape.mesh.data=4"])).run()
    assert sharded["mesh"] == "pod1.data4.model1"
    assert sharded["losses"] == oracle["losses"]
    probe = sharded["collective_probe"]
    assert probe["world"] == 4 and probe["exact"]


@pytest.mark.parametrize("mode", ["dense", "quant"])
def test_sharded_serve_tokens_match_oracle(debug_mesh, mode):
    from repro.api.sessions import ServeSession

    sets = SERVE_SETS + [f"numerics.mode={mode}"]
    oracle = ServeSession(
        build_spec("serve", use_env=False, sets=sets)).run()
    sharded = ServeSession(
        build_spec("serve", use_env=False,
                   sets=sets + ["shape.mesh.data=4"])).run()
    assert np.array_equal(np.asarray(oracle["generated"]),
                          np.asarray(sharded["generated"]))
    assert sharded["collective_probe"]["exact"]


def test_sharded_serve_indivisible_batch_falls_back(debug_mesh):
    from repro.api.sessions import ServeSession
    from repro.runtime.sharding import mesh_fallback_counts
    from repro.telemetry.metrics import default_registry

    default_registry().reset()
    sets = ["arch.id=llama3.2-1b", "shape.batch=3", "shape.prompt_len=8",
            "shape.gen=2", "serving.static=true", "shape.mesh.data=4"]
    out = ServeSession(build_spec("serve", use_env=False, sets=sets)).run()
    assert out["finite"]
    assert "collective_probe" not in out  # replicated: nothing crossed wire
    assert mesh_fallback_counts().get("serve_batch") == 1
