"""Compiles for a described TPU v5e — the kernels of chip_smoke.py's phases at
their real widths, refused here by the chip's own compiler at no chip time.

Nothing runs: a compile that passes is not a chip run. The topology is
described inside a module-scoped fixture (never at import), so every xdist
worker collects the same tests and only the worker that owns this file loads
the TPU library. Keep every such compile in THIS file — a second file can
land on another worker, where the fixture skips in silence.
"""

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from mmlspark_tpu.ops.flash_attention import flash_attention
from mmlspark_tpu.ops.paged_attention import (aligned_page_size,
                                              paged_attention,
                                              paged_attention_window)
from mmlspark_tpu.ops.pallas_kernels import (level_histogram_pallas,
                                             prepare_bins_lanes,
                                             tree_row_block)

# phase B of chip_smoke.py: 12 heads x 64, 16 slots, max_len 2048; pages of
# 16 positions, which the pool rounds up to the int8 sublane tile (32); the
# decode tick's window is 1, a chunked-prefill extension's is prefill_chunk
SLOTS, HEADS, HD, MAX_LEN, CHUNK = 16, 12, 64, 2048, 256
PAGE = {"bf16": aligned_page_size(16, jnp.bfloat16),
        "int8": aligned_page_size(16, jnp.int8)}
# phase C: HIGGS-shaped, 1M x 28, 255 bins, depth-6 trees (32 nodes deepest)
ROWS, FEATS, BINS, NODES = 1_000_000, 28, 255, 32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Shape factory placing every argument on one described v5e chip, with
    the persistent compile cache off (a described-chip executable is written
    to it but cannot be read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    sharding = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)
    yield shape
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compiled_text(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the compiled program"
    return text


def _pool(shape, kv):
    """(pages, [k_scale, v_scale] or [], pages per row) at phase-B widths."""
    page = PAGE[kv]
    per_row = MAX_LEN // page
    n_pages = SLOTS * per_row + 1
    pages = shape((n_pages, HEADS, page, 2 * HD),
                  jnp.int8 if kv == "int8" else jnp.bfloat16)
    scales = ([shape((n_pages, HEADS, page), jnp.bfloat16)] * 2
              if kv == "int8" else [])
    return pages, scales, per_row


def _scale_kw(scales):
    return dict(zip(("k_scale", "v_scale"), scales))


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_read_kernel_compiles(one_chip, kv):
    pages, scales, per_row = _pool(one_chip, kv)
    q = one_chip((SLOTS, HEADS, 1, HD), jnp.bfloat16)
    bt = one_chip((SLOTS, per_row), jnp.int32)
    lens = one_chip((SLOTS,), jnp.int32)

    def read(q, kvp, bt, lens, *scales):
        return paged_attention(q, kvp, bt, lens, interpret=False,
                               **_scale_kw(scales))

    _compiled_text(read, q, pages, bt, lens, *scales)


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("rows,window", [(SLOTS, 1), (1, CHUNK)],
                         ids=["tick", "prefill_chunk"])
def test_paged_fused_window_kernel_compiles(one_chip, kv, rows, window):
    pages, scales, per_row = _pool(one_chip, kv)
    row = one_chip((rows, HEADS, window, HD), jnp.bfloat16)
    bt = one_chip((rows, per_row), jnp.int32)
    pos = one_chip((rows,), jnp.int32)
    active = one_chip((rows,), jnp.bool_)

    def fused(q, kn, vn, kvp, bt, pos, active, *scales):
        return paged_attention_window(q, kn, vn, kvp, bt, pos,
                                      active=active, interpret=False,
                                      **_scale_kw(scales))

    _compiled_text(fused, row, row, row, pages, bt, pos, active, *scales)


def test_mesh_mounted_read_kernel_compiles(topo, one_chip):
    """The dp2 x tp2 mount of chip_smoke.py --chips 4: slots over dp, heads
    over tp, no collective inside the mount."""
    import numpy as np
    mesh = Mesh(np.array(topo.devices[:4]).reshape(2, 2), ("dp", "tp"))

    def shape(dims, dtype, spec):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=NamedSharding(mesh, spec))

    per_row = MAX_LEN // PAGE["bf16"]
    q = shape((SLOTS, HEADS, 1, HD), jnp.bfloat16, P("dp", "tp"))
    pages = shape((SLOTS * per_row + 1, HEADS, PAGE["bf16"], 2 * HD),
                  jnp.bfloat16, P(None, "tp"))
    bt = shape((SLOTS, per_row), jnp.int32, P("dp"))
    lens = shape((SLOTS,), jnp.int32, P("dp"))
    text = _compiled_text(
        functools.partial(paged_attention, interpret=False, mesh=mesh,
                          slot_axis="dp", head_axis="tp"),
        q, pages, bt, lens)
    for collective in ("all-reduce", "all-gather", "all-to-all",
                       "collective-permute"):
        assert collective not in text, f"{collective} inside the mount"


def test_programs_update_the_page_pool_in_place(one_chip):
    """``chip_smoke.py``'s guard at no chip time: the decode tick, a 256-token
    extension and a group insertion at GPT-2 XL's widths (two layers of the
    48: the copies were four a layer) hold no copy of a pool-sized buffer.
    The chip keeps a ``(pages, heads, page, 64)`` bf16 buffer with the page
    index minor-most, a layout the Mosaic call cannot take, so every program
    copied the pool in and out; packed 128 lanes wide it stays row-major."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(os.path.dirname(__file__), os.pardir,
                                   "chip_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    sz = smoke.sizes(small=False)
    from mmlspark_tpu.ops import paged_attention as pa
    from mmlspark_tpu.serving import continuous
    interpret = pa._auto_interpret
    # the engine's programs ask the backend: compile the kernel, and the
    # tick under its TPU options (a name this compiler lacks fails here)
    pa._auto_interpret = continuous._pa_auto_interpret = lambda: False
    try:
        lowered, shapes = smoke.pool_programs(
            sz["pool_decoder"]._replace(layers=2), sz["pool_audit"], one_chip)
        texts = {name: low.compile().as_text()
                 for name, low in lowered.items()}
    finally:
        pa._auto_interpret = continuous._pa_auto_interpret = interpret
        continuous._tick_program.cache_clear()
    assert shapes == {("bfloat16", (577, 25, 16, 128))}
    assert "tpu_custom_call" in texts["jit_tick"]
    assert "slice-start" not in texts["jit_tick"]   # one slice a prefetch
    assert "slice-start" in texts["jit__extend"]
    copies = {name: smoke.pool_copies(text, shapes)
              for name, text in texts.items()}
    assert not any(copies.values()), copies
    # the check can see one: the parent's layout, copied in by the compiler
    assert smoke.pool_copies(
        "%copy.1 = bf16[577,25,16,128]{3,2,1,0:T(8,128)(2,1)} copy(%p)",
        shapes)


@pytest.mark.parametrize("stats", [None, "bfloat16"])
def test_level_histogram_kernel_compiles(one_chip, stats):
    rb = tree_row_block(NODES, BINS)
    xb = one_chip((ROWS, FEATS), jnp.uint8)
    lanes = jax.eval_shape(functools.partial(prepare_bins_lanes,
                                             row_block=rb), xb)
    lanes = one_chip(lanes.shape, lanes.dtype)
    node = one_chip((ROWS,), jnp.int32)
    stat = one_chip((ROWS,), jnp.float32)

    def hist(xb, node, g, h, w, lanes):
        return level_histogram_pallas(xb, node, g, h, w, NODES, BINS,
                                      row_block=rb, interpret=False,
                                      bins_lanes=lanes, stats_dtype=stats)

    _compiled_text(hist, xb, node, stat, stat, stat, lanes)


def test_flash_attention_forward_compiles(one_chip):
    qkv = one_chip((8, 12, 512, 64), jnp.bfloat16)
    _compiled_text(functools.partial(flash_attention, interpret=False),
                   qkv, qkv, qkv)


def test_flash_attention_backward_compiles(one_chip):
    qkv = one_chip((8, 12, 512, 64), jnp.bfloat16)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv)
