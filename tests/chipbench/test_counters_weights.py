"""FLOP and byte counters against hand arithmetic at the published shapes,
and the benchmark's weights: the program's layout, the rules, and layer
regeneration bit for bit."""
import json

import numpy as np
import pytest

from chipbench import weights
from chipbench.families import dense
from _smoke import ROOT


def sizes(name):
    with open(ROOT / "chipbench" / "configs" / f"{name}.json") as f:
        return json.load(f)["sizes"]


GLM = sizes("chatglm3-6b")


def test_dense_counters_by_hand():
    per_layer = 4096 * 4096 + 2 * 4096 * 256 + 4096 * 4096 + 3 * 4096 * 13696
    assert per_layer == 203_948_032
    rows = [(100, 1), (300, 1)]
    flops, nbytes = dense.call_cost(GLM, rows, emit_rows=2)
    attn = 4 * 28 * 32 * 128 * (101 + 301)
    assert flops == 2 * 28 * per_layer * 2 + 2 * 4096 * 65024 * 2 + attn
    kv_per_token = 2 * 2 * 28 * 2 * 128
    assert kv_per_token == 28_672
    weight_bytes = 2 * (28 * (per_layer + 2 * 4096) + 4096 + 4096 * 65024)
    # with the embedding table, the program's bf16 parameters: 12.49 GB
    assert weight_bytes + 2 * 65024 * 4096 == 12_486_909_952
    assert nbytes == (weight_bytes + kv_per_token * 402 + 2 * 4096 * 2
                      + 2 * 65024 * 2)
    # a prefill chunk: 256 new tokens after 512 cached, no logits
    f2, b2 = dense.call_cost(GLM, [(512, 256)], emit_rows=0)
    assert f2 == (2 * 28 * per_layer * 256
                  + 4 * 28 * 32 * 128 * (256 * 512 + 256 * 257 // 2))
    assert b2 == weight_bytes + kv_per_token * 768 + 2 * 4096 * 256


@pytest.mark.parametrize("name,fam", [("chatglm3-6b", dense)])
@pytest.mark.parametrize("smoke", [False, True])
def test_family_layout_is_the_programs(name, fam, smoke):
    """The program builds the leaves the family's reference reads, every
    leaf has a weight rule, and the config file's sizes are the program's."""
    from repro.configs import get_config
    from repro.models import build_model

    cfg = get_config(name, smoke=smoke)
    s = {k: getattr(cfg, k) for k in sizes(name)}
    if not smoke:
        assert s == sizes(name)
    have = {k: tuple(v.shape)
            for k, v in build_model(cfg).abstract_params().items()}
    assert have == fam.param_specs(s)
    for leaf, shape in have.items():
        off, half, bits = fam.rule(leaf, shape[1:] if fam.stacked(leaf)
                                   else shape)
        assert half == weights.pow2(half)
    roles = {n for _, r in fam.layers(s) for n, _ in r.values()}
    assert roles | {"embed", "final_norm", "lm_head"} == set(have)


def test_stacked_weights_regenerate_bit_for_bit():
    import jax.numpy as jnp

    from repro.configs import get_config

    cfg = get_config("chatglm3-6b", smoke=True)
    s = {k: getattr(cfg, k) for k in GLM}
    seed = 2**31 + 4242
    specs = dense.param_specs(s)
    params = weights.params_from_seed(specs, dense, seed)
    root = weights.root_key(seed)
    names = [n for n in specs if dense.stacked(n)][:3] + [
        n for n in specs if not dense.stacked(n)]
    for name in names:
        full = np.asarray(params[name].astype(jnp.float32))
        if dense.stacked(name):
            for i in range(specs[name][0]):
                again = weights.leaf_from_seed(root, dense, name,
                                               specs[name][1:], i)
                np.testing.assert_array_equal(full[i], np.asarray(again))
        else:
            again = weights.leaf_from_seed(root, dense, name, specs[name])
            np.testing.assert_array_equal(full, np.asarray(again))
    other = weights.params_from_seed(specs, dense, seed + 1)
    assert not np.array_equal(np.asarray(params["embed"]),
                              np.asarray(other["embed"]))


def test_weight_values_follow_their_rules():
    import jax.numpy as jnp

    from repro.configs import get_config

    cfg = get_config("chatglm3-6b", smoke=True)
    s = {k: getattr(cfg, k) for k in GLM}
    specs = dense.param_specs(s)
    params = weights.params_from_seed(specs, dense, 5)
    for name, arr in params.items():
        assert arr.dtype == jnp.bfloat16 and arr.shape == specs[name]
        off, half, bits = dense.rule(name, specs[name][1:]
                                     if dense.stacked(name) else specs[name])
        v = np.asarray(arr.astype(jnp.float32))
        assert v.min() >= off - half and v.max() <= off + half
        if bits < 16:   # norm offsets: 1 + v is exact in bf16
            step = half / 2 ** (bits - 1)
            np.testing.assert_array_equal(v / step, np.round(v / step))
