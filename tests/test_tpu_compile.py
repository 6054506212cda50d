"""The paged decode kernels compile for a TPU v5e at qwen2-7b's shapes (and
the tiered kernel at starcoder2-3b's heads too).

Interpret mode cannot show what the TPU compiler refuses (block shapes off
the (8, 128) tiling, vector shape casts Mosaic lacks), so these tests
lower each kernel for a described -- not attached -- v5e chip with
interpret mode off.  The topology is described inside a fixture, never at
import: only one process may load the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attn import paged as pg

# qwen2-7b decode at serving shapes: 16 lanes, 4 KV heads of 7 query heads
# each, head dim 128, 16-token pages, 2048 pool pages, 256-page tables
LANES, G, GROUP, D, PS, P, NP = 16, 4, 7, 128, 16, 2048, 256
# starcoder2-3b's heads: 2 KV heads of 12 query heads each
SC_G, SC_GROUP = 2, 12


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler / library lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip cannot be read back from the
    # persistent cache: keep it out of the way while these run
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _hlo(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _paged(quantized):
    def fn(q, k, ks, v, vs, bt, lengths):
        return pg.paged_decode_attn(q, k, ks, v, vs, bt, lengths,
                                    interpret=False)
    dt = jnp.int8 if quantized else jnp.bfloat16
    return fn, [((P, G, PS, D), dt), ((P, G, PS), jnp.float32)] * 2


def _tiered(window, g=G):
    def fn(q, kh, vh, k8, ks, v8, vs, bt, lengths):
        return pg.paged_decode_attn_tiered(q, kh, vh, k8, ks, v8, vs, bt,
                                           lengths, window=window,
                                           interpret=False)
    hot = ((P, g, PS, D), jnp.bfloat16)
    warm = [((P, g, PS, D), jnp.int8), ((P, g, PS), jnp.float32)]
    return fn, [hot, hot] + warm * 2


@pytest.mark.parametrize("case", ["bf16", "int8", "tiered",
                                  "tiered_window", "tiered_starcoder"])
def test_paged_kernel_compiles_for_v5e(one_chip, case):
    fn, pools = {"bf16": lambda: _paged(False),
                 "int8": lambda: _paged(True),
                 "tiered": lambda: _tiered(0),
                 "tiered_window": lambda: _tiered(512),
                 "tiered_starcoder": lambda: _tiered(0, SC_G)}[case]()
    g, group = (SC_G, SC_GROUP) if case == "tiered_starcoder" else (G, GROUP)
    q = ((LANES, g * group, D), jnp.bfloat16)
    bt = ((LANES, NP), jnp.int32)
    lengths = ((LANES,), jnp.int32)
    hlo = _hlo(one_chip, fn, q, *pools, bt, lengths)
    assert "tpu_custom_call" in hlo
    if case.startswith("tiered"):
        # the benchmark's attn_kernel_ms finds the kernel by the name of
        # its HLO instruction, and paged_attn_roofline by its custom call:
        # one Mosaic call, named for the kernel
        assert hlo.count('custom_call_target="tpu_custom_call"') == 1
        assert re.search(r"%paged_decode_attn_tiered(\.\d+)? = [^\n]*"
                         r"custom-call\(", hlo)
