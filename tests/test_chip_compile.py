"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

Interpret mode accepts kernels that the chip's compiler (Mosaic) refuses,
such as a one-row load at an index not provably a multiple of the 8-row
sublane tile.  These tests compile each kernel family with
``interpret=False`` at the widths of the served models (chatglm3-6b,
zamba2-1.2b, rwkv6-3b) for a chip that is described but not attached, so
such a refusal fails here instead of on the chip.  Nothing runs.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every test worker
imports this file.
"""
import os

import jax
import pytest

import repro.bench.specs  # noqa: F401  (registers the kernel specs)
from repro.bench.registry import get_spec

#: (family, shape): decode attention at chatglm3-6b (hq=32, hkv=2, d=128)
#: and zamba2-1.2b (hq=hkv=32, d=64) geometry over a 4-slot, 16-page x 16
#: table, bf16 and int8 KV; the gemms at 4 slots x chatglm3's d_model x
#: d_ff; the recurrences at one 64-step chunk of rwkv6-3b (40 heads of 64)
#: and of zamba2-1.2b's mamba2 mixer (64 heads, P = N = 64).
CASES = [
    ("flash_decode_paged", dict(b=4, hq=32, hkv=2, d=128, pages=16, ps=16)),
    ("flash_decode_paged", dict(b=4, hq=32, hkv=2, d=128, pages=16, ps=16,
                                kv_int8=1)),
    ("flash_decode_paged", dict(b=4, hq=32, hkv=32, d=64, pages=16, ps=16)),
    ("flash_decode_paged", dict(b=4, hq=32, hkv=32, d=64, pages=16, ps=16,
                                kv_int8=1)),
    ("flash_decode", dict(b=4, hq=32, hkv=2, d=128, s=256)),
    ("apr_matmul", dict(m=4, k=4096, n=13696)),
    ("apr_matmul_fused", dict(m=4, k=4096, n=13696)),
    ("quant_matmul", dict(m=4, k=4096, n=13696)),
    ("quant_matmul_fused", dict(m=4, k=4096, n=13696)),
    ("rwkv6", dict(b=1, t=64, h=40, d=64)),
    ("mamba2", dict(b=1, t=64, h=64, p=64, n=64)),
]


def _case_id(case):
    name, shape = case
    return name + "-" + "_".join(f"{k}{v}" for k, v in shape.items())


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("case", CASES, ids=_case_id)
def test_kernel_compiles_for_v5e(case, one_chip):
    name, shape = case
    spec = get_spec(name)
    arrays = jax.eval_shape(lambda: spec.make_inputs(shape, "bfloat16", 0))
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in arrays]
    cfg = spec.default_config(shape)
    compiled = jax.jit(
        lambda *a: spec.run(a, cfg, False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ref = jax.eval_shape(lambda: spec.ref(spec.make_inputs(shape,
                                                           "bfloat16", 0)))
    assert compiled.out_info.shape == ref.shape
