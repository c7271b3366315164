"""Sharding-spec construction on the (modeled) production 16×16 mesh.

Spec assignment is pure shape arithmetic — ``param_specs`` /
``input_specs`` / ``enforce_divisible`` only read ``mesh.shape`` and
``mesh.axis_names`` — so these tests model the forced 512-device mesh
with ``jax.sharding.AbstractMesh`` and run on the single real CPU device.

The pinned contract (DESIGN.md §11): for EVERY registered smoke config,
every surviving spec entry divides its mesh axes evenly, and every
non-dividing assignment is downgraded to replication EXPLICITLY —
reported by ``enforce_divisible``, never silently padded.  The two LM
workload archs additionally pin their exact fallback sets, so a rule
change that silently re-shards (or stops sharding) a smoke tensor fails
loudly here.
"""
import functools

import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ARCH_NAMES, ShapeConfig, get_smoke_config
from repro.models import transformer as T
from repro.models.sharding import enforce_divisible, input_specs, param_specs

MESH = AbstractMesh((16, 16), ("data", "model"))


def _axis_size(entry) -> int:
    axes = entry if isinstance(entry, tuple) else (entry,)
    size = 1
    for a in axes:
        size *= MESH.shape[a]
    return size


def _leaves_with_specs(cfg, specs):
    shapes = jax.eval_shape(functools.partial(T.init_params, cfg),
                            jax.random.key(0))
    return zip(jax.tree.leaves(shapes),
               jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)))


@pytest.mark.parametrize("arch", ARCH_NAMES)
class TestEverySmokeConfig:
    def test_param_specs_divide_or_fall_back(self, arch):
        cfg = get_smoke_config(arch)
        specs, fallbacks = enforce_divisible(cfg, MESH)
        # 1) every surviving entry divides evenly
        for leaf, spec in _leaves_with_specs(cfg, specs):
            for dim, entry in enumerate(spec):
                if entry is not None:
                    assert leaf.shape[dim] % _axis_size(entry) == 0, (
                        f"{arch}: {spec} does not divide {leaf.shape}")
        # 2) every downgrade is explicit and true: the reported dim
        # really does not divide the axis it was assigned
        for path, dim, entry, dim_size in fallbacks:
            assert dim_size % _axis_size(entry) != 0, (
                f"{arch}: {path} reported as fallback but divides")

    def test_input_specs_divide_or_fall_back(self, arch):
        cfg = get_smoke_config(arch)
        for b, s in ((2, 32), (16, 32), (64, 128)):
            shape = ShapeConfig("t", seq_len=s, global_batch=b,
                                kind="train")
            sds, specs = input_specs(cfg, shape, MESH)
            for name, spec in specs.items():
                for dim, entry in enumerate(spec):
                    if entry is not None:
                        assert (sds[name].shape[dim] % _axis_size(entry)
                                == 0), (f"{arch} {name}: {spec} vs "
                                        f"{sds[name].shape}")

    def test_small_batch_replicates(self, arch):
        # a 2-row batch cannot split 16 ways: the rule must fall back to
        # replication, not emit a non-dividing spec
        cfg = get_smoke_config(arch)
        shape = ShapeConfig("t", seq_len=32, global_batch=2, kind="train")
        _, specs = input_specs(cfg, shape, MESH)
        key = "embeds" if cfg.frontend == "audio_stub" else "tokens"
        assert specs[key][0] is None

    def test_enforce_divisible_idempotent(self, arch):
        cfg = get_smoke_config(arch)
        once, _ = enforce_divisible(cfg, MESH)
        twice, again = enforce_divisible(cfg, MESH, specs=once)
        assert again == []
        assert jax.tree.all(jax.tree.map(
            lambda a, b: a == b, once, twice,
            is_leaf=lambda x: isinstance(x, P)))


class TestWorkloadArchPins:
    """The two LM-workload smoke configs pin their exact fallback sets."""

    def test_rwkv6_fallbacks(self):
        _, fallbacks = enforce_divisible(get_smoke_config("rwkv6-7b"),
                                         MESH)
        # 4 rwkv heads (and the 224-wide ffn gate) cannot split model=16
        names = sorted({p.split("/")[-1] for p, *_ in fallbacks})
        assert names == ["ln_out", "u", "w0", "w_g", "w_k", "w_lora_b",
                         "w_o", "w_r", "w_v"]
        assert all(dim_size in (4, 224) for *_, dim_size in fallbacks)

    def test_danube_fallbacks(self):
        _, fallbacks = enforce_divisible(
            get_smoke_config("h2o-danube-3-4b"), MESH)
        # 4 q heads / 2 kv heads cannot split model=16; everything else
        # (embeddings, ffn, lm head) divides
        assert sorted(p.split("/")[-1] for p, *_ in fallbacks) == \
            ["wo", "wq"]
        assert all(dim_size == 4 for *_, dim_size in fallbacks)

    def test_untouched_specs_still_shard(self):
        # the enforcement must not over-replicate: leaves that DO divide
        # keep their model-axis assignment (the storage-scaling claim of
        # the pod LM backend depends on at least the embedding sharding)
        cfg = get_smoke_config("rwkv6-7b")
        specs, _ = enforce_divisible(cfg, MESH)
        flat = {
            "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path): spec
            for path, spec in jax.tree_util.tree_flatten_with_path(
                specs, is_leaf=lambda x: isinstance(x, P))[0]}
        sharded = [p for p, s in flat.items()
                   if any(e is not None for e in s)]
        assert any(p.endswith("tok") for p in sharded)
        assert any(p.endswith("w") for p in sharded)      # lm head


class TestBackendSpecComposition:
    def test_basis_specs_mirror_param_specs(self):
        # the pod LM backend prepends a replicated lane axis to every
        # param spec; the pair must stay tree-aligned and divisible
        cfg = get_smoke_config("rwkv6-7b")
        specs, _ = enforce_divisible(cfg, MESH)
        bspecs = jax.tree.map(lambda s: P(*((None,) + tuple(s))), specs,
                              is_leaf=lambda x: isinstance(x, P))
        for spec, bspec in zip(
                jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)),
                jax.tree.leaves(bspecs,
                                is_leaf=lambda x: isinstance(x, P))):
            assert bspec[0] is None
            assert tuple(bspec[1:]) == tuple(spec)
