"""Ahead-of-time compiles of the scan kernels for a described TPU v5e.

Interpret mode never shows a tiling, lowering or fast-memory refusal;
the TPU compiler does, and it runs here without a chip. Each case
compiles the per-shard scan plan (``engine.make_shard_topk_fn``: the
routed or cluster-major kernel plus its plan and merge) at the
``list-dual-encoder`` serving widths — d=768, c=300, k=20 and the
capacity Geo-Glue's 2,849,754 objects get at c=300 — and checks that the
compiled program holds the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the test runner's workers all
import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import engine
from repro.core import index as il

D, C, K, T = 768, 300, 20, 1000
CAP = il.default_capacity(2_849_754, C)
EMB_DTYPE = {"bf16": jnp.bfloat16, "int8": jnp.int8}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile_scan(one_chip, *, backend, precision, batch, cr,
                  filtered=False):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [spec((T,), jnp.float32),                      # w_hat
            spec((C, CAP, D), EMB_DTYPE[precision]),      # buf_emb
            spec((C, CAP, 2), jnp.float32),               # buf_loc
            spec((C, CAP), jnp.int32),                    # buf_ids
            spec((C, CAP), jnp.float32)]                  # buf_scale
    if filtered:
        args.append(spec((C, CAP, 3), jnp.int32))         # buf_attrs
    args += [spec((batch, D), jnp.float32),               # q_emb
             spec((batch, 2), jnp.float32),               # q_loc
             spec((batch, 2), jnp.float32),               # w_st
             spec((batch, cr), jnp.int32)]                # top_c
    if filtered:
        args.append(spec((batch, 4), jnp.int32))         # q_filt
    fn = engine.make_shard_topk_fn(k=K, backend=backend, interpret=False,
                                   precision=precision, filtered=filtered)
    return fn.lower(*args).compile()


@pytest.mark.parametrize("batch,cr", [(64, 2), (4096, 1)])
@pytest.mark.parametrize("precision", ["bf16", "int8"])
@pytest.mark.parametrize("backend", ["pallas", "pallas-cm"])
def test_scan_compiles_for_v5e(one_chip, backend, precision, batch, cr):
    compiled = _compile_scan(one_chip, backend=backend, precision=precision,
                             batch=batch, cr=cr)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("backend", ["pallas", "pallas-cm"])
def test_filtered_scan_compiles_for_v5e(one_chip, backend):
    compiled = _compile_scan(one_chip, backend=backend, precision="int8",
                             batch=64, cr=2, filtered=True)
    assert "tpu_custom_call" in compiled.as_text()
