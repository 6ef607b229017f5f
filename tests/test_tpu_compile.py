"""Ahead-of-time Mosaic compiles of the serving main-path kernels for a
described TPU v5e, at the published widths of qwen3_8b (and one
deepseek_moe_16b expert bank).

Nothing here runs a kernel: each test lowers and compiles one kernel for
a chip that is described, not attached, which is where Mosaic refuses a
block shape, a reshape of the minor dim or an op it cannot legalize —
failures interpret mode never shows. The topology is described inside a
module-scoped fixture (never at import), so every test worker collects
the same tests and only the worker running this file loads the TPU
compiler; where it cannot be described the tests skip from the fixture.
The persistent compilation cache is turned off around the module: a
compile for a described chip cannot be read back without the chip.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import bitpack
from repro.kernels.kv_decode import kv_decode
from repro.kernels.packed_matmul import packed_matmul, packed_matmul_batched
from repro.kernels.paged_attention import paged_attention
from repro.kernels.take import take_rows

# qwen3_8b published widths
D, FF, VOCAB, H, HKV, HD = 4096, 12288, 151936, 32, 8, 128
SLOTS, SEQ, PAGE = 4, 2048, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _words(n, bits):
    return bitpack.packed_group_words(n, bits)


@pytest.mark.parametrize("bits", [8, 12, 16])
@pytest.mark.parametrize("k,n", [(D, FF), (FF, D), (D, HKV * HD)])
def test_packed_matmul_compiles(spec, bits, k, n):
    _compile(lambda x, w: packed_matmul(x, w, bits, n, interpret=False),
             spec((SLOTS, k), jnp.bfloat16),
             spec((k, _words(n, bits)), jnp.uint32))


@pytest.mark.parametrize("k,n", [(D, FF), (FF, D), (D, HKV * HD)])
def test_packed_matmul_prefill_rows_compiles(spec, k, n):
    """A one-pass prefill chunk: 16 slots x 16 tokens reach the kernel
    as one (256, k) operand, two of its 128-row M blocks."""
    _compile(lambda x, w: packed_matmul(x, w, 8, n, interpret=False),
             spec((16, 16, k), jnp.bfloat16),
             spec((k, _words(n, 8)), jnp.uint32))


@pytest.mark.parametrize("bits", [8, 12, 16])
def test_packed_matmul_transpose_compiles(spec, bits):
    """The tied-unembed orientation: contraction over the packed axis of
    the full (vocab, d_model) table."""
    _compile(lambda x, w: packed_matmul(x, w, bits, VOCAB, transpose=True,
                                        interpret=False),
             spec((SLOTS, D), jnp.bfloat16),
             spec((VOCAB, _words(D, bits)), jnp.uint32))


@pytest.mark.parametrize("bits", [8, 16])
@pytest.mark.parametrize("transpose", [False, True])
def test_packed_matmul_batched_compiles(spec, bits, transpose):
    """One deepseek_moe_16b expert bank: 64 experts, 2048 -> 1408 (the
    1408-wide axis pads to whole tiles)."""
    e, d, f, cap = 64, 2048, 1408, 24
    kdim, n = (f, d) if transpose else (d, f)
    _compile(lambda x, w: packed_matmul_batched(
                 x, w, bits, n, transpose=transpose, interpret=False),
             spec((e, cap, kdim), jnp.bfloat16),
             spec((e, d, _words(f, bits)), jnp.uint32))


@pytest.mark.parametrize("bits", [8, 16])
def test_take_rows_compiles(spec, bits):
    _compile(lambda t, i: take_rows(t, i, bits, D, out_dtype=jnp.bfloat16,
                                    interpret=False),
             spec((VOCAB, _words(D, bits)), jnp.uint32),
             spec((SLOTS * 16,), jnp.int32))


@pytest.mark.parametrize("bits", [8, 16])
def test_kv_decode_compiles(spec, bits):
    row = spec((SLOTS, SEQ, _words(HKV * HD, bits)), jnp.uint32)
    _compile(lambda q, k, v, n: kv_decode(q, k, v, n, bits, HKV,
                                          interpret=False),
             spec((SLOTS, H, HD), jnp.bfloat16), row, row,
             spec((SLOTS,), jnp.int32))


@pytest.mark.parametrize("bits", [0, 8, 16])
def test_paged_attention_compiles(spec, bits):
    n_pages = SLOTS * SEQ // PAGE
    pool = (spec((n_pages + 1, PAGE, _words(HKV * HD, bits)), jnp.uint32)
            if bits else
            spec((n_pages + 1, PAGE, HKV * HD), jnp.bfloat16))
    _compile(lambda q, k, v, t, n: paged_attention(q, k, v, t, n, bits, HKV,
                                                   interpret=False),
             spec((SLOTS, H, HD), jnp.bfloat16), pool, pool,
             spec((SLOTS, SEQ // PAGE), jnp.int32),
             spec((SLOTS,), jnp.int32))


def test_kernels_keep_their_names(spec):
    """The profiler names a kernel's executions by its instruction, which
    ``pallas_call(name=...)`` fixes: the benchmark's readers find
    ``packed_matmul.N`` and ``paged_attention.N`` whatever wraps them."""
    n_pages = SLOTS * SEQ // PAGE
    pool = spec((n_pages + 1, PAGE, _words(HKV * HD, 16)), jnp.uint32)

    def step(x, w, q, k, v, t, n):
        return (packed_matmul(x, w, 8, FF, interpret=False),
                paged_attention(q, k, v, t, n, 16, HKV, interpret=False))

    text = _compile(step, spec((SLOTS, D), jnp.bfloat16),
                    spec((D, _words(FF, 8)), jnp.uint32),
                    spec((SLOTS, H, HD), jnp.bfloat16), pool, pool,
                    spec((SLOTS, SEQ // PAGE), jnp.int32),
                    spec((SLOTS,), jnp.int32)).as_text()
    kernels = re.findall(r"%([\w.-]+) = [^\n]*tpu_custom_call", text)
    assert sorted(re.sub(r"\.\d+$", "", k) for k in kernels) == [
        "packed_matmul", "paged_attention"]
