"""Sharding-rule unit tests: logical axes -> PartitionSpec, ZeRO-1 specs,
batch specs, cache specs — pure functions, no devices needed."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, SHAPES, get_config, input_specs
from repro.models import build_model
from repro.parallel.sharding import (ParallelContext, logical_to_spec,
                                     param_specs, zero1_spec)
from repro.train.step import cache_spec


class FakeMesh:
    """Just enough mesh for spec-level tests (no devices)."""
    def __init__(self, shape):
        self._shape = shape
        self.axis_names = tuple(shape)

    @property
    def shape(self):
        return self._shape


def ctx(pod=False):
    mesh = FakeMesh({"pod": 2, "data": 16, "model": 16} if pod
                    else {"data": 16, "model": 16})
    return ParallelContext(mesh=mesh, dp_axes=("pod", "data") if pod else ("data",))


def test_tp_axes_map_to_model():
    c = ctx()
    assert logical_to_spec(("embed", "ff"), c) == P(None, "model")
    assert logical_to_spec(("heads", "embed"), c) == P("model", None)
    assert logical_to_spec(("vocab", "embed"), c) == P("model", None)


def test_kv_heads_replicated_when_not_divisible():
    c = ctx()
    assert logical_to_spec(("embed", "kv_heads"), c, kv_heads=4) == P(None, None)
    assert logical_to_spec(("embed", "kv_heads"), c, kv_heads=32) == P(None, "model")


@pytest.mark.parametrize("hkv,degree,repeat", [(4, 4, 1), (8, 4, 1),
                                                (2, 4, 2), (1, 4, 4)])
def test_tp_plan_repeats_kv_heads_below_the_mesh(hkv, degree, repeat):
    from repro.parallel.tp import plan_tp
    cfg = dataclasses.replace(get_config("chatglm3-6b", smoke=True),
                              num_heads=8, num_kv_heads=hkv, d_ff=128)
    plan = plan_tp(cfg, degree)
    assert plan.kv_repeat == repeat
    assert plan.local_cfg.num_kv_heads == hkv * repeat // degree


def test_tp_plan_rejects_kv_heads_that_do_not_divide():
    from repro.parallel.tp import plan_tp
    cfg = dataclasses.replace(get_config("chatglm3-6b", smoke=True),
                              num_heads=12, num_kv_heads=3, d_ff=128)
    with pytest.raises(ValueError, match="KV heads"):
        plan_tp(cfg, 4)


def test_experts_on_data_axis():
    c = ctx()
    assert logical_to_spec(("layers", "experts", "embed", "ff"), c) == \
        P(None, "data", None, "model")


def test_zero1_shards_first_free_divisible_dim():
    c = ctx()
    assert zero1_spec(P(None, "model"), (4096, 14336), c) == P("data", "model")
    # already data-sharded (experts): untouched
    assert zero1_spec(P("data", None), (128, 64), c) == P("data", None)
    # nothing divisible: untouched
    assert zero1_spec(P(None,), (31,), c) == P(None,)


def test_dp_degree_and_batch_spec():
    c = ctx(pod=True)
    assert c.dp_degree == 32
    assert c.batch_spec(extra_dims=1) == P(("pod", "data"), None)


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_param_specs_cover_every_leaf(arch):
    cfg = ARCHS[arch]
    bundle = build_model(cfg)
    logical = bundle.logical_axes()
    specs = param_specs(logical, ctx(), kv_heads=cfg.num_kv_heads)
    abstract = bundle.abstract_params()
    assert set(specs) == set(abstract)
    for name, spec in specs.items():
        shape = abstract[name].shape
        assert len(spec) <= len(shape), name
        # every sharded dim must divide the mesh axis size
        for dim, entry in zip(shape, list(spec)):
            if entry == "model":
                assert dim % 16 == 0, (arch, name, shape, spec)
            if entry == "data":
                assert dim % 16 == 0, (arch, name, shape, spec)


@pytest.mark.parametrize("arch", sorted(ARCHS))
@pytest.mark.parametrize("shape_name", ["decode_32k", "long_500k"])
def test_cache_specs_divisible(arch, shape_name):
    cfg = ARCHS[arch]
    from repro.configs.shapes import applicable
    shape = SHAPES[shape_name]
    if not applicable(cfg, shape):
        pytest.skip("long_500k inapplicable (full attention)")
    c = ctx()
    specs = input_specs(cfg, shape)
    cspec = cache_spec(cfg, c, specs["cache"])
    for key, leaf in specs["cache"].items():
        sp = cspec[key]
        for dim, entry in zip(leaf.shape, list(sp)):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            total = 1
            for a in axes:
                total *= c.mesh.shape[a]
            assert dim % total == 0, (arch, shape_name, key, leaf.shape, sp)


def test_padded_vocab_divides_tp():
    for cfg in ARCHS.values():
        assert cfg.padded_vocab % 16 == 0
        assert cfg.padded_vocab >= cfg.vocab_size
        assert cfg.padded_vocab - cfg.vocab_size < 256
