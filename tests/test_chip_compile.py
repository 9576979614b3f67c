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
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from mmlspark_tpu.ops.flash_attention import flash_attention
from mmlspark_tpu.ops.grouped_matmul import TILE, grouped_swiglu
from mmlspark_tpu.ops.kda_attention import kda_decode_step
from mmlspark_tpu.ops.lightning_attention import lightning_decode_step
from mmlspark_tpu.ops.paged_attention import (aligned_page_size,
                                              paged_attention,
                                              paged_attention_latent,
                                              paged_attention_selected,
                                              paged_attention_window)
from mmlspark_tpu.ops.pallas_kernels import (level_histogram_pallas,
                                             prepare_bins_lanes,
                                             tree_row_block)

# phase B of chip_smoke.py: 12 heads x 64, 16 slots, max_len 2048; the
# decode tick's window is 1, a chunked-prefill extension's is prefill_chunk.
# Pages: the 128 positions the decoder derives from that max_len (2048 / 16),
# and an explicit page_size=16, which the pool rounds up to the stored
# dtype's sublane tile (32 for int8)
SLOTS, HEADS, HD, MAX_LEN, CHUNK = 16, 12, 64, 2048, 256
STORED = {"bf16": jnp.bfloat16, "int8": jnp.int8}
by_page = pytest.mark.parametrize("page", [128, 16],
                                  ids=["derived", "page16"])
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


def _pool(shape, kv, page):
    """(pages, [k_scale, v_scale] or [], pages per row) at phase-B widths."""
    page = aligned_page_size(page, STORED[kv])
    per_row = MAX_LEN // page
    n_pages = SLOTS * per_row + 1
    pages = shape((n_pages, HEADS, page, 2 * HD), STORED[kv])
    scales = ([shape((n_pages, HEADS, page), jnp.bfloat16)] * 2
              if kv == "int8" else [])
    return pages, scales, per_row


def _scale_kw(scales):
    return dict(zip(("k_scale", "v_scale"), scales))


@by_page
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_read_kernel_compiles(one_chip, kv, page):
    pages, scales, per_row = _pool(one_chip, kv, page)
    q = one_chip((SLOTS, HEADS, 1, HD), jnp.bfloat16)
    bt = one_chip((SLOTS, per_row), jnp.int32)
    lens = one_chip((SLOTS,), jnp.int32)

    def read(q, kvp, bt, lens, *scales):
        return paged_attention(q, kvp, bt, lens, interpret=False,
                               **_scale_kw(scales))

    _compiled_text(read, q, pages, bt, lens, *scales)


@by_page
@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("rows,window", [(SLOTS, 1), (1, CHUNK)],
                         ids=["tick", "prefill_chunk"])
def test_paged_fused_window_kernel_compiles(one_chip, kv, rows, window, page):
    pages, scales, per_row = _pool(one_chip, kv, page)
    row = one_chip((rows, HEADS, window, HD), jnp.bfloat16)
    bt = one_chip((rows, per_row), jnp.int32)
    pos = one_chip((rows,), jnp.int32)
    active = one_chip((rows,), jnp.bool_)

    def fused(q, kn, vn, kvp, bt, pos, active, *scales):
        return paged_attention_window(q, kn, vn, kvp, bt, pos,
                                      active=active, interpret=False,
                                      **_scale_kw(scales))

    _compiled_text(fused, row, row, row, pages, bt, pos, active, *scales)


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("rows,window", [(8, 1), (1, 256)],
                         ids=["tick", "prefill_chunk"])
def test_gpt2xl_fused_window_kernel_compiles(one_chip, rows, window, kv):
    """The generation cell's own shape (benchmarks/workloads/
    gpt2xl_generate_closed.json): 8 slots, 25 heads of 64, pages of 64, 16 a
    slot; the ragged sweep's bound is traced, so one program serves every
    batch of contexts. Under ``_VMEM_LIMIT_BYTES`` (the compiler refuses a
    kernel over it) over bf16 and int8 pages: the tick's state is a row a
    head with an accumulator a packed row wide, the chunk's a row a
    query."""
    heads, page, per_row = 25, 64, 16
    row = one_chip((rows, heads, window, HD), jnp.bfloat16)
    n_pages = 1 + 8 * per_row + per_row
    scales = ([one_chip((n_pages, heads, page), jnp.bfloat16)] * 2
              if kv == "int8" else [])

    def fused(q, kn, vn, kvp, bt, pos, active, *scales):
        return paged_attention_window(q, kn, vn, kvp, bt, pos, active=active,
                                      interpret=False, **_scale_kw(scales))

    text = _compiled_text(
        fused, row, row, row,
        one_chip((n_pages, heads, page, 2 * HD), STORED[kv]),
        one_chip((rows, per_row), jnp.int32), one_chip((rows,), jnp.int32),
        one_chip((rows,), jnp.bool_), *scales)
    assert "_pa_fused_call" in text         # the name the benchmark's trace finds


@by_page
def test_mesh_mounted_read_kernel_compiles(topo, one_chip, page):
    """The dp2 x tp2 mount of chip_smoke.py --chips 4: slots over dp, heads
    over tp, no collective inside the mount."""
    import numpy as np
    mesh = Mesh(np.array(topo.devices[:4]).reshape(2, 2), ("dp", "tp"))

    def shape(dims, dtype, spec):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=NamedSharding(mesh, spec))

    per_row = MAX_LEN // page
    q = shape((SLOTS, HEADS, 1, HD), jnp.bfloat16, P("dp", "tp"))
    pages = shape((SLOTS * per_row + 1, HEADS, page, 2 * HD),
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


@pytest.fixture(scope="module")
def gpt2xl_programs(one_chip):
    """``chip_smoke.pool_programs`` at GPT-2 XL's widths (two layers of the
    48), in the pages the decoder derives from ``max_len`` 1024 (64
    positions, 16 a slot), compiled for the chip: the decode tick, a
    256-token extension and a group insertion, and beside them the batched
    prefill that feeds the insertion (it carries no pool, so the smoke's
    guard leaves it out). -> ``({program: text}, pool shapes, smoke)``."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(os.path.dirname(__file__), os.pardir,
                                   "chip_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    sz = smoke.sizes(small=False)
    from mmlspark_tpu.models.zoo.transformer import init_transformer
    from mmlspark_tpu.ops import paged_attention as pa
    from mmlspark_tpu.serving import continuous
    cfg, audit = sz["pool_decoder"]._replace(layers=2), sz["pool_audit"]
    interpret = pa._auto_interpret
    # the engine's programs ask the backend: compile the kernel, and the
    # tick under its TPU options (a name this compiler lacks fails here)
    pa._auto_interpret = continuous._pa_auto_interpret = lambda: False
    try:
        lowered, shapes = smoke.pool_programs(cfg, audit, one_chip)
        params = jax.tree.map(
            lambda a: one_chip(a.shape, cfg.dtype),
            jax.eval_shape(lambda: init_transformer(cfg, seed=0)))
        g, n = audit["group"], audit["rows_len"]
        lowered["jit__prefill"] = continuous._prefill_program(
            cfg, audit["max_len"]).lower(
                params, one_chip((g, n), jnp.int32), one_chip((g,), jnp.int32))
        texts = {name: low.compile().as_text()
                 for name, low in lowered.items()}
    finally:
        pa._auto_interpret = continuous._pa_auto_interpret = interpret
        continuous._tick_program.cache_clear()
    return texts, shapes, smoke


def test_programs_update_the_page_pool_in_place(gpt2xl_programs):
    """``chip_smoke.py``'s guard at no chip time: the decode tick, a 256-token
    extension and a group insertion at GPT-2 XL's widths hold no copy of a
    pool-sized buffer (the copies were four a layer), and each kernel's
    scoped VMEM fits ``_VMEM_LIMIT_BYTES`` (the compiler refuses one that
    does not).
    The chip keeps a ``(pages, heads, page, 64)`` bf16 buffer with the page
    index minor-most, a layout the Mosaic call cannot take, so every program
    copied the pool in and out; packed 128 lanes wide it stays row-major."""
    texts, shapes, smoke = gpt2xl_programs
    assert shapes == {("bfloat16", (145, 25, 64, 128))}
    assert "tpu_custom_call" in texts["jit_tick"]
    assert "slice-start" not in texts["jit_tick"]   # one slice a prefetch
    assert "slice-start" in texts["jit__extend"]
    # the kernel's sweep is scheduled once a tick: both layers' calls take
    # the same two step vectors (operands 1 and 2, after the traced bound)
    calls = [ln.split("custom-call(")[1].split(", ")[:3]
             for ln in texts["jit_tick"].splitlines()
             if " custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(calls) == 2 and calls[0] == calls[1], calls
    copies = {name: smoke.pool_copies(text, shapes)
              for name, text in texts.items()}
    assert not any(copies.values()), copies
    # the check can see one: the parent's layout, copied in by the compiler
    assert smoke.pool_copies(
        "%copy.1 = bf16[145,25,64,128]{3,2,1,0:T(8,128)(2,1)} copy(%p)",
        shapes)


@pytest.mark.parametrize("program", ["jit_tick", "jit__extend",
                                     "jit__prefill", "jit__insert_group"])
def test_programs_read_the_embedding_tables_where_they_lie(gpt2xl_programs,
                                                           program):
    """GPT-2 XL's tables are 1600 wide, no multiple of the chip's 128 lanes,
    so the chip keeps them column-major; a gather wants them row-major and
    the compiler relaid the whole token table (161 MB read, 161 MB written)
    in front of it in every tick, extension and prefill, and the position
    table (3.3 MB) beside it. ``transformer._rows`` reads both in place: no
    ``copy`` of either table's shape, at top level or inside a fusion, in
    any program that embeds (the group insertion takes no parameters: it
    holds none by construction, and stays in the list as the control)."""
    texts, _, smoke = gpt2xl_programs
    tables = {("bfloat16", (50257, 1600)), ("bfloat16", (1024, 1600))}
    assert not smoke.pool_copies(texts[program], tables)
    if program != "jit__insert_group":
        assert "bf16[50257,1600]" in texts[program]     # it does embed
    # the check can see one: the parent's line, from its compiled tick
    assert smoke.pool_copies(
        "%copy = bf16[50257,1600]{1,0:T(8,128)(2,1)} copy(%t.1), "
        "sharding={replicated}", tables)


# the hybrid cell (benchmarks/workloads/sala_docqa_closed8.json): MiniCPM-SALA
# at its published widths, 8 slots of 32,768 positions in pages of 64
SALA = dict(slots=8, max_len=32768, page=64, chunk=256, heads=32, kv_heads=2,
            hd=128, topk=64)


@pytest.mark.parametrize("listed", [64, 128], ids=["top64", "dense128"])
def test_selected_block_kernel_compiles(one_chip, listed):
    """One query a row over ``listed`` pages of one KV head each (the top-64
    blocks, or 128 while a row sits under ``dense_len``), 16 query heads a KV
    head: the sparse layers' decode attention, a block of EIGHT listed pages
    a grid step (``select_block``: 8 x 32 KB), eight page operands on the
    one pool, which is not copied; inside ``_VMEM_LIMIT_BYTES`` (the compiler
    refuses a kernel over it)."""
    z = SALA
    per = z["max_len"] // z["page"]
    pool = (1 + z["slots"] * per, z["kv_heads"], z["page"], 2 * z["hd"])
    fn = functools.partial(paged_attention_selected, interpret=False)
    args = (one_chip((z["slots"], z["kv_heads"], z["heads"] // z["kv_heads"],
                      z["hd"]), jnp.bfloat16),
            one_chip(pool, jnp.bfloat16),
            one_chip((z["slots"], per), jnp.int32),
            one_chip((z["slots"], z["kv_heads"], listed), jnp.int32),
            one_chip((z["slots"],), jnp.int32))
    text = _compiled_text(fn, *args)
    # the name the benchmark's trace finds
    (line,) = [ln for ln in text.splitlines()
               if "tpu_custom_call" in ln and "_pa_select_call" in ln]
    ops = _call_operands(line)      # table, list, lengths, query, 8 x pool
    assert (len(ops), max(ops.count(op) for op in set(ops))) == (12, 8)
    assert not [ln for ln in text.splitlines()
                if "= bf16[%s]" % ",".join(map(str, pool)) in ln
                and " copy(" in ln]
    jaxpr = str(jax.make_jaxpr(fn)(*args))
    assert f"grid=(8, 2, {listed // 8})" in jaxpr
    assert "f32[1,16,512]" in jaxpr             # a whole block's scores


def test_lightning_step_kernel_compiles_in_place(one_chip):
    """The linear-attention decode step on 8 slots x 32 heads of (128 x 128)
    float32 state, the donated state aliased in and out: no copy of it."""
    z = SALA
    row = one_chip((z["slots"], z["heads"], z["hd"]), jnp.float32)
    state = one_chip((z["slots"], z["heads"], z["hd"], z["hd"]), jnp.float32)
    text = jax.jit(
        functools.partial(lightning_decode_step, interpret=False),
        donate_argnums=(3,)).lower(
            row, row, row, state, one_chip((z["slots"],), bool)
        ).compile().as_text()
    assert "_lightning_step_call" in text and "tpu_custom_call" in text
    assert " copy(" not in text


def _riding_tick(progs, cfg, z, params, pool, ints, one_chip, width):
    """The tick that carries a ``width``-token prefill window (what a hybrid
    engine at one step a dispatch runs for every chunk), lowered at the
    cell's shapes."""
    per = z["max_len"] // z["page"]
    return progs._tick_program(
        cfg, z["page"], z["max_len"], 1, None, False, True,
        chunk=True).lower(
            params, ints(z["slots"]), ints(z["slots"]),
            one_chip((z["slots"],), bool), pool.buffers,
            ints(z["slots"], per), ints(z["slots"]),
            ints(1, width), ints(1), ints(1, per), ints(), ints(1))


@pytest.fixture(scope="module")
def sala_programs(one_chip):
    """The hybrid engine's programs lowered at the cell's shapes (the first
    two layers of the eight: one sparse, one lightning), as
    ``chip_smoke.pool_programs`` does it for the dense block."""
    import json

    from benchmarks import run as bench_run
    from mmlspark_tpu.models.zoo import hybrid
    from mmlspark_tpu.ops import paged_attention as pa
    from mmlspark_tpu.serving import continuous as progs
    from mmlspark_tpu.serving.kv_pool import PagedKVPool
    z = SALA
    with open(os.path.join(bench_run.HERE, "configs",
                           "minicpm_sala_l8.json")) as fh:
        config = json.load(fh)
    config.update(num_hidden_layers=2, mixer_types=config["mixer_types"][:2])
    cfg = bench_run.load_by_path("drivers", "generate_docs").program_config(
        config, z["max_len"])
    per = z["max_len"] // z["page"]
    reference = bench_run.load_by_path("references", config["reference"])
    params = jax.tree.map(          # the reference's weights are jnp: shapes
        lambda a: one_chip(a.shape, a.dtype),       # as the decoder holds them
        jax.eval_shape(lambda: hybrid.serving_layout(
            cfg, reference.make_weights(config, 0))))
    pool = PagedKVPool(cfg, page_size=z["page"], residency=False,
                       make_buffer=one_chip, slots=z["slots"],
                       slot_positions=z["max_len"],
                       num_pages=1 + z["slots"] * per + per)
    ints = lambda *dims: one_chip(dims, jnp.int32)          # noqa: E731
    interpret = pa._auto_interpret
    pa._auto_interpret = progs._pa_auto_interpret = lambda: False
    try:
        tick = progs._tick_program(
            cfg, z["page"], z["max_len"], 1, None, False, True).lower(
                params, ints(z["slots"]), ints(z["slots"]),
                one_chip((z["slots"],), bool), pool.buffers,
                ints(z["slots"], per), ints(z["slots"]))
        extend = progs._extend_program(cfg, z["page"], z["max_len"], True)
        lowered = {"tick": tick}
        for name, width in (("chunk", z["chunk"]), ("suffix", 64)):
            lowered[name] = extend.lower(
                params, ints(1, width), ints(1), pool.buffers, ints(1, per),
                ints(), ints(1))
        lowered["riding"] = _riding_tick(progs, cfg, z, params, pool, ints,
                                         one_chip, 64)
        yield {name: low.compile().as_text()
               for name, low in lowered.items()}, pool
    finally:
        pa._auto_interpret = progs._pa_auto_interpret = interpret
        progs._tick_program.cache_clear()
        progs._extend_program.cache_clear()


@pytest.mark.parametrize("program", ["tick", "chunk", "suffix", "riding"])
def test_hybrid_programs_compile_and_keep_the_pool_in_place(sala_programs,
                                                            program):
    """The hybrid tick, a 256-token prefill chunk, a 64-token suffix window
    and the tick that carries such a window (``riding``) at the published
    widths (8 slots, 32,768 positions): they compile for the chip, the ticks
    hold both decode kernels, and none copies a pool-sized buffer (pages,
    compressed keys or the state rows)."""
    texts, pool = sala_programs
    text = texts[program]
    if program in ("tick", "riding"):
        assert "_pa_select_call" in text and "_lightning_step_call" in text
    names = {"bfloat16": "bf16", "float32": "f32"}
    for layer in pool.buffers:
        for buf in layer.values():
            shape = f"{names[buf.dtype.name]}[{','.join(map(str, buf.shape))}]"
            copies = [ln.strip()[:120] for ln in text.splitlines()
                      if f"= {shape}" in ln and " copy(" in ln]
            assert not copies, copies


def _weight_copies(text, shapes):
    """``[(shape, source layout, source, result layout)]`` of every ``copy``
    whose result has one of ``shapes``: the layouts as the compiled text
    prints them (minor-to-major order, tiling, ``S(1)`` = VMEM), the source
    followed through one ``bitcast`` to the parameter or the prefetch
    (``copy-done``) under it."""
    line_of = {m.group(1): ln for ln in text.splitlines()
               if (m := re.match(r"\s*(%[\w.-]+) = ", ln))}
    made = re.compile(r"= bf16\[([\d,]+)\](\{[^}]*\}) ([\w-]+)\(([^,)]*)")
    found = []
    for ln in text.splitlines():
        m = made.search(ln)
        if not (m and m.group(3) == "copy" and m.group(1) in shapes):
            continue
        src = made.search(line_of[m.group(4)])
        under = (made.search(line_of[src.group(4)])
                 if src.group(3) == "bitcast" else src)
        found.append((m.group(1), src.group(2), under.group(3), m.group(2)))
    return found


@pytest.mark.parametrize("program", ["tick", "chunk", "suffix", "riding"])
def test_hybrid_programs_read_their_qkv_weights_where_they_lie(sala_programs,
                                                               program):
    """ROADMAP S1's hybrid half, cured (PR 50; read by PR 41): a lightning
    or sparse layer's q/k/v weights, stored ``[in, heads * hd]``, were laid
    out anew as ``[heads * hd, in]`` on their way into VMEM, every tick,
    for the product that emits heads-major rows (0.76 ms a tick on the
    chip). The decoder now holds them as that product reads them
    (``hybrid.serving_layout``, which the fixture's shapes went through),
    and no program copies an array of a weight's shape, in either
    orientation."""
    texts, _ = sala_programs
    copies = _weight_copies(texts[program],
                            {"4096,4096", "256,4096", "4096,256"})
    for row in copies:
        print("weight copy: bf16[%s] %s (%s) -> %s" % row)
    assert not copies
    # and the parser sees a plain fetch for what it is
    assert _weight_copies(
        "%p = bf16[4096,4096]{1,0:T(8,128)(2,1)} parameter(0)\n"
        "%c = bf16[4096,4096]{1,0:T(8,128)(2,1)S(1)} copy(%p)",
        {"4096,4096"}) == [("4096,4096", "{1,0:T(8,128)(2,1)}", "parameter",
                            "{1,0:T(8,128)(2,1)S(1)}")]


# the routed cell (benchmarks/workloads/lingflash_reason_closed32.json):
# Ling-3.0-flash's share at its published widths, 32 slots of 4,096 positions
# in the pages the decoder derives (256: 16 a slot)
LING = dict(slots=32, max_len=4096, page=256, chunk=256, heads=32, hd=128,
            latent=512, row=640, hidden=2560, width=768, held=128, top=8)


def test_kda_step_kernel_compiles_in_place(one_chip):
    """The delta-rule decode step on 32 slots x 32 heads of (128 x 128)
    float32 state, the donated state aliased in and out: no copy of it."""
    z = LING
    row = one_chip((z["slots"], z["heads"], z["hd"]), jnp.float32)
    state = one_chip((z["slots"], z["heads"], z["hd"], z["hd"]), jnp.float32)
    text = jax.jit(
        functools.partial(kda_decode_step, interpret=False),
        donate_argnums=(5,)).lower(
            row, row, row, row, one_chip((z["slots"], z["heads"]),
                                         jnp.float32),
            state, one_chip((z["slots"],), bool)).compile().as_text()
    assert "_kda_step_call" in text and "tpu_custom_call" in text
    assert " copy(" not in text


def test_latent_read_kernel_compiles(one_chip):
    """32 absorbed query heads a row over one 640-wide latent row a cached
    position (the first 512 values the value too): the mla layer's tick."""
    z = LING
    per = z["max_len"] // z["page"]
    text = _compiled_text(
        functools.partial(paged_attention_latent, v_width=z["latent"],
                          scale=192 ** -0.5, interpret=False),
        one_chip((z["slots"], z["heads"], z["row"]), jnp.float32),
        one_chip((1 + z["slots"] * per, 1, z["page"], z["row"]),
                 jnp.bfloat16),
        one_chip((z["slots"], per), jnp.int32),
        one_chip((z["slots"],), jnp.int32))
    assert "_pa_latent_call" in text
    # sixteen pages a slot: the sweep stays a page a step, the call the
    # program it was before the sweep went in blocks (one page operand beside
    # the grid bound, the sweep's three vectors, the table, the lengths and
    # the query; tree against tree, jaxpr and lowered text: CHANGES.md, PR 44)
    assert _latent_operands(text) == (8, 1)


def _latent_operands(text):
    """``(operands, of which the pool)`` of the compiled program's one
    ``_pa_latent_call`` custom call."""
    (line,) = [ln for ln in text.splitlines()
               if "tpu_custom_call" in ln and "_pa_latent_call" in ln]
    ops = _call_operands(line)
    return len(ops), max(ops.count(op) for op in set(ops))


def _call_operands(line):
    """The operand names of a compiled text's ``custom-call(...)`` line."""
    return re.findall(r"%[\w.\-]+", line.split("custom-call(")[1].split(
        "), custom_call_target")[0])


def _expert_product_compiles(one_chip, tokens, top, held, d, f, gated=True):
    """The grouped product's launch for the described chip at the most tiles
    ``tokens`` rows of ``top`` pairs can fill over ``held`` experts of
    ``(d, 2f | f)`` and ``(f, d)``: the call keeps its jitted name (the
    benchmark's ``trace_ops`` read it), an expert's two weight blocks
    double-buffered, a run's ``RUN`` row tiles, the two slots of its output
    rows and the wider body's temporaries fit the kernels' VMEM limit, and
    nothing beside the call copies an expert's weight block. Returns the
    tiles."""
    from mmlspark_tpu.ops import paged_attention as pa
    from mmlspark_tpu.ops.grouped_matmul import RUN
    from mmlspark_tpu.parallel.moe import held_tiles
    wide = f * (2 if gated else 1)
    rows = RUN * TILE
    block = 2 * (d * wide + f * d)
    vmem = (2 * block                           # the expert, and the next
            + 2 * rows * d * 2 + 2 * rows * d * 4   # row tiles in, rows out
            + rows * (wide * 4 + f * 6 + d * 4))    # x W, the hidden, the rows
    assert vmem < pa._VMEM_LIMIT_BYTES, vmem
    tiles = held_tiles(tokens * top, held, TILE)
    text = _compiled_text(
        functools.partial(grouped_swiglu, interpret=False, gated=gated),
        one_chip((tiles * TILE, d), jnp.bfloat16),
        one_chip((tiles,), jnp.int32), one_chip((), jnp.int32),
        one_chip((held, d, wide), jnp.bfloat16),
        one_chip((held, f, d), jnp.bfloat16))
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1 and "_moe_experts_call" in calls[0]
    shapes = [f"bf16[1,{d},{wide}]", f"bf16[1,{f},{d}]",
              f"bf16[{held},{d},{wide}]", f"bf16[{held},{f},{d}]"]
    copies = [ln.strip()[:120] for ln in text.splitlines()
              if " copy(" in ln and any(f"= {sh}" in ln for sh in shapes)]
    assert not copies, copies
    return tiles, block


@pytest.mark.parametrize("tokens", [32, 256, 32 + 256],
                         ids=["tick", "prefill_chunk", "carrying_tick"])
def test_grouped_expert_product_compiles(one_chip, tokens):
    """The routed experts' product over 128 held experts of width 768 at
    the most tiles a tick's (a chunk's, a carrying tick's) pairs can fill."""
    z = LING
    _expert_product_compiles(one_chip, tokens, z["top"], z["held"],
                             z["hidden"], z["width"])


def _share_programs(one_chip, z, config_file, driver, cut):
    """A stage-share engine's tick and prefill chunk lowered and compiled at
    the cell's shapes ``z`` on three of its layers (``cut``), as
    ``sala_programs`` does it. Yields ``({"tick", "chunk"}: compiled text,
    the pool)``."""
    import json

    from benchmarks import run as bench_run
    from mmlspark_tpu.ops import paged_attention as pa
    from mmlspark_tpu.serving import continuous as progs
    from mmlspark_tpu.serving.kv_pool import PagedKVPool
    with open(os.path.join(bench_run.HERE, "configs", config_file)) as fh:
        config = json.load(fh)
    config.update(dict(num_hidden_layers=3, layers_held=[0, 10, 11]), **cut)
    cfg = bench_run.load_by_path("drivers", driver).program_config(
        config, z["max_len"])
    per = z["max_len"] // z["page"]
    reference = bench_run.load_by_path("references", config["reference"])
    params = jax.tree.map(
        lambda a: one_chip(a.shape, a.dtype),
        jax.eval_shape(lambda: reference.make_weights(config, 0)))
    pool = PagedKVPool(cfg, page_size=z["page"], residency=False,
                       make_buffer=one_chip, slots=z["slots"],
                       slot_positions=z["max_len"],
                       num_pages=z.get("pages")
                       or 1 + z["slots"] * per + z["slots"])
    ints = lambda *dims: one_chip(dims, jnp.int32)          # noqa: E731
    interpret = pa._auto_interpret
    pa._auto_interpret = progs._pa_auto_interpret = lambda: False
    try:
        tick = progs._tick_program(
            cfg, z["page"], z["max_len"], 1, None, False, True).lower(
                params, ints(z["slots"]), ints(z["slots"]),
                one_chip((z["slots"],), bool), pool.buffers,
                ints(z["slots"], per), ints(z["slots"]))
        extend = progs._extend_program(cfg, z["page"], z["max_len"], True)
        chunk = extend.lower(params, ints(1, z["chunk"]), ints(1),
                             pool.buffers, ints(1, per), ints(), ints(1))
        riding = _riding_tick(progs, cfg, z, params, pool, ints, one_chip,
                              z["chunk"])
        yield {"tick": tick.compile().as_text(),
               "chunk": chunk.compile().as_text(),
               "riding": riding.compile().as_text()}, pool
    finally:
        pa._auto_interpret = progs._pa_auto_interpret = interpret
        progs._tick_program.cache_clear()
        progs._extend_program.cache_clear()


@pytest.fixture(scope="module")
def ling_programs(one_chip):
    """The routed engine's programs at the cell's shapes (layers 0, 10 and
    11 of the seven: kda under the dense feed-forward, kda and mla under the
    routed one)."""
    yield from _share_programs(one_chip, LING, "ling3_flash_ep4_l7.json",
                               "generate_ling", {})


def _one_read_of_the_experts(text, cfg):
    """A compiled program multiplies each routed layer's experts in ONE
    grouped product (the tick that carries a window too: its rows and the
    window's lanes share it) and copies no array shaped as a layer's expert
    weights."""
    r = cfg.routed
    shapes = [f"bf16[{r.held},{cfg.d_model},{2 * r.d_expert}]",
              f"bf16[{r.held},{r.d_expert},{cfg.d_model}]"]
    lines = text.splitlines()
    calls = sum("tpu_custom_call" in ln and "_moe_experts_call" in ln
                for ln in lines)
    assert calls == sum(kind == "moe" for kind in cfg.ffn)
    copies = [ln.strip()[:120] for ln in lines
              if " copy(" in ln and any(f"= {sh}" in ln for sh in shapes)]
    assert not copies, copies


@pytest.mark.parametrize("program", ["tick", "chunk", "riding"])
def test_routed_programs_compile_and_keep_the_pool_in_place(ling_programs,
                                                            program):
    """The routed tick, a 256-token prefill chunk and the tick that carries
    one (``riding``) at the published widths (32 slots, 4,096 positions):
    they compile for the chip, the ticks hold the three kernels and the plain
    one no sequential loop (a gather of rows), each routed layer's experts
    are multiplied once and never copied, and none copies a pool-sized
    buffer: the states, the convolution tails' slots aside (2 MB, laid out
    by the chip), or the latent pages, whose row is padded to whole registers
    so that the pool stays row-major."""
    texts, pool = ling_programs
    text = texts[program]
    if program in ("tick", "riding"):
        for name in ("_moe_experts_call", "_kda_step_call",
                     "_pa_latent_call"):
            assert name in text
    if program == "tick":
        assert " while(" not in text
    _one_read_of_the_experts(text, pool.cfg)
    names = {"bfloat16": "bf16", "float32": "f32"}
    for layer in pool.buffers:
        for key, buf in layer.items():
            if key == "conv":
                continue
            shape = f"{names[buf.dtype.name]}[{','.join(map(str, buf.shape))}]"
            copies = [ln.strip()[:120] for ln in text.splitlines()
                      if f"= {shape}" in ln and " copy(" in ln]
            assert not copies, copies


# the conv + grouped-query cell (lfm2_ragchat_closed32): 32 query heads over
# 8 KV heads of 64, 32 slots of 5,120 positions in pages of 256, 64 experts
# of 1536 all held, top-4, a 512-token prefill chunk
LFM2 = dict(slots=32, heads=32, kv_heads=8, hd=64, page=256, max_len=5120,
            hidden=2048, width=1536, held=64, top=4, chunk=512)


def _gqa_decode_compiles(one_chip, z, scale=None):
    """The grouped-query tick kernel lowered for the described chip at a
    cell's shapes ``z``: the call under its jitted name, the pool (``pages``
    of them, else every slot's at ``max_len``) aliased and never copied."""
    from mmlspark_tpu.ops.paged_attention import paged_attention_gqa
    per = z["max_len"] // z["page"]
    pool = (z.get("pages") or 1 + z["slots"] * per, z["kv_heads"], z["page"],
            2 * z["hd"])
    text = jax.jit(functools.partial(paged_attention_gqa, interpret=False,
                                     scale=scale),
                   donate_argnums=(3,)).lower(
        one_chip((z["slots"], z["heads"], z["hd"]), jnp.bfloat16),
        one_chip((z["slots"], z["kv_heads"], z["hd"]), jnp.bfloat16),
        one_chip((z["slots"], z["kv_heads"], z["hd"]), jnp.bfloat16),
        one_chip(pool, jnp.bfloat16), one_chip((z["slots"], per), jnp.int32),
        one_chip((z["slots"],), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in text and "_pa_gqa_call" in text
    shape = f"bf16[{','.join(map(str, pool))}]"
    assert not [ln for ln in text.splitlines()
                if f"= {shape}" in ln and " copy(" in ln]


def test_grouped_query_decode_kernel_compiles_in_place(one_chip):
    """The gqa layer's tick: four query heads fold a KV head's page block,
    the fresh K/V row scattered in the same launch, the pool aliased."""
    _gqa_decode_compiles(one_chip, LFM2)


@pytest.mark.parametrize("tokens", [32, 512, 32 + 512],
                         ids=["tick", "prefill_chunk", "carrying_tick"])
def test_whole_layer_expert_product_compiles(one_chip, tokens):
    """The routed product with every expert held: a grid step is one 18.9 MB
    expert ((2048, 3072) + (1536, 2048) bf16), 37.7 MB double-buffered,
    beside a run's 128 rows in and out, inside the 64 MiB the kernels are
    given; a carrying tick's 544 rows x 4 fill up to 200 tiles."""
    z = LFM2
    tiles, block = _expert_product_compiles(
        one_chip, tokens, z["top"], z["held"], z["hidden"], z["width"])
    assert block == 18_874_368
    assert tiles == {32: 72, 512: 192, 544: 200}[tokens]


@pytest.fixture(scope="module")
def lfm2_programs(one_chip):
    """The conv + gqa engine's programs at the cell's shapes (layers 0, 10
    and 11 of the nine: conv under the dense feed-forward, gqa and conv
    under the routed one)."""
    yield from _share_programs(
        one_chip, LFM2, "lfm2_24b_a2b_pp5_l9.json", "generate_lfm2",
        dict(layer_types=["conv", "full_attention", "conv"]))


@pytest.mark.parametrize("program", ["tick", "chunk", "riding"])
def test_conv_gqa_programs_compile_and_keep_the_pool_in_place(lfm2_programs,
                                                              program):
    """The tick, a 512-token prefill chunk and the tick that carries one
    (``riding``) at the published widths (32 slots, 5,120 positions): they
    compile for the chip, the ticks hold the grouped-query kernel and the
    experts' product, ONE a routed layer with no copy of its weights, the
    plain tick no sequential loop (a slice a row of the tails would be one),
    and none copies the page pool (the tails' slots aside: 0.26 MB a layer,
    laid out by the chip)."""
    texts, pool = lfm2_programs
    text = texts[program]
    _one_read_of_the_experts(text, pool.cfg)
    if program in ("tick", "riding"):
        assert "_pa_gqa_call" in text
    if program == "tick":
        assert " while(" not in text
    for layer in pool.buffers:
        for key, buf in layer.items():
            if key == "conv":
                continue
            shape = f"bf16[{','.join(map(str, buf.shape))}]"
            copies = [ln.strip()[:120] for ln in text.splitlines()
                      if f"= {shape}" in ln and " copy(" in ln]
            assert not copies, copies


# the shared-context latent cell (glmflash_repoctx_shared32): 20 heads over
# latent pages of 640-wide rows, 32 slots of 32,768 positions in pages of 256
# (128 a slot), a pool of 1,024 pages, 64 experts of 1536 and a shared one,
# a 512-token window
GLM = dict(slots=32, heads=20, page=256, max_len=32768, pages=1024, row=640,
           latent=512, chunk=512)


def test_latent_read_kernel_compiles_at_twenty_heads(one_chip):
    """20 absorbed float32 query heads a row, no multiple of the sublane
    tile: they go in as 24 and 20 contexts come out."""
    z = GLM
    per = z["max_len"] // z["page"]
    fn = functools.partial(paged_attention_latent, v_width=z["latent"],
                           scale=256 ** -0.5, interpret=False)
    args = (one_chip((z["slots"], z["heads"], z["row"]), jnp.float32),
            one_chip((z["pages"], 1, z["page"], z["row"]), jnp.bfloat16),
            one_chip((z["slots"], per), jnp.int32),
            one_chip((z["slots"],), jnp.int32))
    text = _compiled_text(fn, *args)
    assert "_pa_latent_call" in text and "f32[32,1,24,640]" in text
    assert jax.eval_shape(fn, *args).shape == (32, 20, 512)
    # 128 pages a slot: a grid step is a block of FOUR pages of one row, four
    # page operands on the one pool (and what each holds a step: four more
    # vectors), under a traced grid bound; the pool is not copied, and no
    # array joins a block's pages (4 x 256 rows of 640): the scores of a
    # whole block stand side by side, its pages never
    assert _latent_operands(text) == (15, 4)
    pool = "bf16[1024,1,256,640]"
    assert not [ln for ln in text.splitlines()
                if f"= {pool}" in ln and " copy(" in ln]
    jaxpr = str(jax.make_jaxpr(fn)(*args))
    assert "grid=(DynamicGridDim,)" in jaxpr
    assert jaxpr.count("Blocked(block_size=256), Blocked(block_size=640)") == 4
    assert "f32[1,24,1024]" in jaxpr            # a whole block's scores
    assert not re.search(r"\[[0-9,]*1024,(640|512)\]", jaxpr.replace(pool, ""))


@pytest.fixture(scope="module")
def glm_programs(one_chip):
    """The all-latent engine's programs at the cell's shapes and at the
    cell's DEPTH: layer 0 and the six routed layers."""
    yield from _share_programs(
        one_chip, GLM, "glm47_flash_l7.json", "generate_glm",
        dict(num_hidden_layers=7, layers_held=[0, 7, 8, 9, 10, 11, 12]))


@pytest.mark.parametrize("program", ["tick", "chunk", "riding"])
def test_latent_programs_compile_and_hold_a_tile_not_the_slot(glm_programs,
                                                              program):
    """The seven-layer tick, a 512-token window alone and the tick that
    carries one (``riding``) at the published widths (32 slots of 32,768
    positions over 1,024 pages): they compile for the chip; the ticks hold
    ONE absorbed kernel an mla layer and one grouped product a routed layer
    with no copy of its weights; the plain tick holds no sequential loop; a
    window's attention is one ``while`` a layer under a traced bound whose
    temporaries are a tile's: no array spans the slot's 32,768 positions (the
    parent rebuilt ``bf16[1,20,32768,448]``, 0.59 GB a layer); and none
    copies the latent page pool."""
    texts, pool = glm_programs
    text = texts[program]
    lines = text.splitlines()
    _one_read_of_the_experts(text, pool.cfg)
    if program in ("tick", "riding"):
        calls = [ln for ln in lines
                 if "tpu_custom_call" in ln and "_pa_latent_call" in ln]
        assert len(calls) == 7
        # the block sweep is built once a tick: every layer's call takes the
        # same grid bound, and in the plain tick the same sweep, table and
        # lengths (its first six operands; around a window's temporaries the
        # compiler brings the 4 KB vectors into VMEM again a layer)
        same = 6 if program == "tick" else 1
        assert len({tuple(_call_operands(ln)[:same]) for ln in calls}) == 1
        assert all(len(_call_operands(ln)) == 15 for ln in calls)
    whiles = sum(" while(" in ln for ln in lines)
    assert whiles == (0 if program == "tick" else 7)
    assert not re.search(r"\[[0-9,]*32768[0-9,]*\]", text)
    shape = f"bf16[{','.join(map(str, pool.buffers[0]['kv'].shape))}]"
    assert shape == "bf16[1024,1,256,640]"
    copies = [ln.strip()[:120] for ln in lines
              if f"= {shape}" in ln and " copy(" in ln]
    assert not copies, copies


# the state-space cell (nemotronsuper_chat_closed32): Mamba-2 of 128 heads of
# 64 on a state 128 wide in 8 groups, 32 query heads over 2 KV heads of 128,
# 128 of 512 relu^2 experts of 2688 in a 1,024 latent at 22 a token, 32 slots
# of 4,096 positions in pages of 256, a 256-token window
NEMOTRON = dict(slots=32, page=256, max_len=4096, chunk=256, heads=32,
                kv_heads=2, hd=128, ssm_heads=128, ssm_hd=64, state=128,
                groups=8, held=128, latent=1024, width=2688)


@pytest.mark.parametrize("cell", ["NEMOTRON", "GRANITE"])
def test_ssm_step_kernel_compiles_in_place(one_chip, cell):
    """The state-space decode step on 32 slots x 128 heads of a (64 x 128)
    float32 state held in pairs, the donated state aliased in and out: no
    copy of it, and the step's blocks fit its VMEM limit. Eight groups: four
    whole groups a grid step; ONE group of 64 pairs (4 MiB): two grid steps
    of 32 that read the group's one ``B`` and ``C``."""
    from mmlspark_tpu.ops.ssm_step import pairs_a_step, ssm_decode_step
    z = globals()[cell]
    assert pairs_a_step(z["groups"], z["state"] * 2 * z["ssm_hd"] * 4,
                        z["ssm_heads"] // 2 // z["groups"]) == {
        "NEMOTRON": (4, 8), "GRANITE": (1, 32)}[cell]
    state = (z["slots"], z["ssm_heads"] // 2, z["state"], 2 * z["ssm_hd"])
    shared = one_chip((z["slots"], z["groups"], z["state"]), jnp.float32)
    text = jax.jit(
        functools.partial(ssm_decode_step, interpret=False),
        donate_argnums=(4,)).lower(
            one_chip((z["slots"], z["ssm_heads"], z["ssm_hd"]), jnp.float32),
            one_chip((z["slots"], z["ssm_heads"]), jnp.float32), shared,
            shared, one_chip(state, jnp.float32),
            one_chip((z["slots"],), bool)).compile().as_text()
    assert "_ssm_step_call" in text and "tpu_custom_call" in text
    shape = f"f32[{','.join(map(str, state))}]"
    assert not [ln for ln in text.splitlines()
                if f"= {shape}" in ln and " copy(" in ln]


def test_grouped_query_decode_kernel_compiles_at_sixteen_a_head(one_chip):
    """The gqa layer's tick at 32 query heads over 2 KV heads: two whole
    groups of state rows fold one KV head's page block, the pool aliased."""
    _gqa_decode_compiles(one_chip, NEMOTRON)


@pytest.mark.parametrize("tokens", [32, 32 + 256],
                         ids=["tick", "carrying_tick"])
def test_latent_expert_product_compiles(one_chip, tokens):
    """The non-gated grouped product over 128 held experts of (1024 x 2688)
    at 22 pairs a token: 172 tiles a plain tick can fill, 524 a tick that
    carries a 256-token window; the blocks fit the kernel's VMEM limit."""
    z = NEMOTRON
    tiles, _ = _expert_product_compiles(one_chip, tokens, 22, z["held"],
                                        z["latent"], z["width"], gated=False)
    assert tiles == {32: 172, 288: 524}[tokens]


def _whole_depth_programs(one_chip, z, config_file, driver):
    """A cell's tick and the tick that carries a ``z["chunk"]``-token
    window, lowered and compiled at the cell's shapes and at the cell's
    DEPTH (every layer the configuration file holds). Yields ``({"tick",
    "riding"}: compiled, the pool)``."""
    import json

    from benchmarks import run as bench_run
    from mmlspark_tpu.ops import paged_attention as pa
    from mmlspark_tpu.serving import continuous as progs
    from mmlspark_tpu.serving.kv_pool import PagedKVPool
    with open(os.path.join(bench_run.HERE, "configs", config_file)) as fh:
        config = json.load(fh)
    cfg = bench_run.load_by_path("drivers", driver).program_config(
        config, z["max_len"])
    reference = bench_run.load_by_path("references", config["reference"])
    params = jax.tree.map(
        lambda a: one_chip(a.shape, a.dtype),
        jax.eval_shape(lambda: reference.make_weights(config, 0)))
    per = z["max_len"] // z["page"]
    pool = PagedKVPool(cfg, page_size=z["page"], residency=False,
                       make_buffer=one_chip, slots=z["slots"],
                       slot_positions=z["max_len"],
                       num_pages=z.get("pages")
                       or 1 + z["slots"] * per + z["slots"])
    ints = lambda *dims: one_chip(dims, jnp.int32)          # noqa: E731
    interpret = pa._auto_interpret
    pa._auto_interpret = progs._pa_auto_interpret = lambda: False
    try:
        tick = progs._tick_program(
            cfg, z["page"], z["max_len"], 1, None, False, True).lower(
                params, ints(z["slots"]), ints(z["slots"]),
                one_chip((z["slots"],), bool), pool.buffers,
                ints(z["slots"], per), ints(z["slots"]))
        riding = _riding_tick(progs, cfg, z, params, pool, ints, one_chip,
                              z["chunk"])
        yield {"tick": tick.compile(), "riding": riding.compile()}, pool
    finally:
        pa._auto_interpret = progs._pa_auto_interpret = interpret
        progs._tick_program.cache_clear()


@pytest.fixture(scope="module")
def nemotron_programs(one_chip):
    """The state-space engine's tick and the tick that carries a 256-token
    window at the cell's DEPTH: all eleven published layers (six block
    layers)."""
    yield from _whole_depth_programs(one_chip, NEMOTRON,
                                     "nemotron3_super_ep4_l11.json",
                                     "generate_nemotron")


@pytest.mark.parametrize("program", ["tick", "riding"])
def test_state_space_programs_compile_and_keep_the_pool_in_place(
        nemotron_programs, program):
    """The eleven-layer tick and the tick that carries a 256-token window at
    the published widths (32 slots, 4,096 positions): they compile for the
    chip and hold ONE state-space step an ``M`` layer, ONE grouped product an
    ``E`` layer with no copy of its experts, ONE grouped-query call; the
    plain tick holds no sequential loop (a slice a row of the tails would be
    one); neither copies a layer's states (134 MB) or the page pool; and the
    carrying tick's temporaries at 22 pairs a token stay a small part of the
    chip (125 MB where the weights are 9.3 GB)."""
    compiled, pool = nemotron_programs
    text = compiled[program].as_text()
    lines = text.splitlines()
    for name, n in (("_ssm_step_call", 5), ("_moe_experts_call", 5),
                    ("_pa_gqa_call", 1)):
        assert sum("tpu_custom_call" in ln and name in ln
                   for ln in lines) == n, name
    if program == "tick":
        assert " while(" not in text
    z = NEMOTRON
    shapes = [f"bf16[{z['held']},{z['latent']},{z['width']}]",
              f"bf16[{z['held']},{z['width']},{z['latent']}]"]
    names = {"bfloat16": "bf16", "float32": "f32"}
    for layer in pool.buffers:
        shapes += [
            f"{names[buf.dtype.name]}[{','.join(map(str, buf.shape))}]"
            for key, buf in layer.items() if key != "conv"]
    assert "f32[32,64,128,128]" in shapes
    copies = [ln.strip()[:120] for ln in lines
              if " copy(" in ln and any(f"= {sh}" in ln for sh in shapes)]
    assert not copies, copies
    memory = compiled[program].memory_analysis()
    assert memory.temp_size_in_bytes < 256 << 20
    # the states and the pages are donated and updated in place
    assert memory.alias_size_in_bytes > 5 * 32 * 64 * 128 * 128 * 4


# 32 slots of 65,536 positions in pages of 256 (1,400 pages of 8 KV heads
# of 128: 1 MiB each), Mamba-2 of 128 heads of 64 on a 128-wide state in
# ONE group, 36 of 72 experts of 768 held, top-10, a 512-token prefill chunk
GRANITE = dict(slots=32, page=256, max_len=65536, pages=1400, chunk=512,
               heads=32, kv_heads=8, hd=128, ssm_heads=128, ssm_hd=64,
               state=128, groups=1, hidden=4096, width=768, held=36, top=10,
               vocab=50176)


def test_grouped_query_decode_kernel_compiles_at_a_mebibyte_a_page(one_chip):
    """The gqa layer's tick at 8 KV heads of 128 over block tables 256
    pages wide, scores under the model's own scale: the pool (1.47 GB)
    aliased, no copy of it."""
    _gqa_decode_compiles(one_chip, GRANITE, scale=1 / 128)


@pytest.mark.parametrize("tokens", [32, 32 + 512],
                         ids=["tick", "carrying_tick"])
def test_expert_product_compiles_at_thirty_six_of_seventy_two(one_chip,
                                                              tokens):
    """The grouped product over 36 held experts of (4096, 1536) + (768,
    4096) bf16 (18.9 MB a grid step, LFM2's bytes in another shape) at 10
    pairs a token: neither count a power of two or a multiple of 128."""
    z = GRANITE
    tiles, block = _expert_product_compiles(
        one_chip, tokens, z["top"], z["held"], z["hidden"], z["width"])
    assert block == 18_874_368
    assert tiles == {32: 56, 544: 376}[tokens]


@pytest.fixture(scope="module")
def granite_programs(one_chip):
    """The engine's tick and the tick that carries a 512-token window at the
    cell's DEPTH: all ten layers held, nine ssm to one gqa, a routed
    feed-forward in each."""
    yield from _whole_depth_programs(one_chip, GRANITE,
                                     "granite4_h_small_ep2_l10.json",
                                     "generate_granite")


@pytest.mark.parametrize("program", ["tick", "riding"])
def test_one_group_programs_compile_and_keep_the_pool_in_place(
        granite_programs, program):
    """The ten-layer tick and the tick that carries a 512-token window at
    the published widths (32 slots of 65,536 positions, 1,400 pages): they
    compile for the chip and hold ONE state-space step an ssm layer, ONE
    grouped product a layer with no copy of its experts, ONE grouped-query
    call; the plain tick holds no sequential loop; neither copies a layer's
    states (134 MB), the page pool (1.47 GB) or the token table that is the
    head too (411 MB: the tied product reads it where it lies); and the
    carrying tick's temporaries are the gqa window's: it gathers its row's
    WHOLE block table (256 pages = 268 MB, gathered, laid out by head and
    split into K and V: 1.65 GB at 64 lanes, 1.68 GB at 512, so the chunk's
    width is not what they follow: ROADMAP S13), a tenth of the chip beside
    12.2 GB of weights and pool."""
    compiled, pool = granite_programs
    text = compiled[program].as_text()
    lines = text.splitlines()
    for name, n in (("_ssm_step_call", 9), ("_moe_experts_call", 10),
                    ("_pa_gqa_call", 1)):
        assert sum("tpu_custom_call" in ln and name in ln
                   for ln in lines) == n, name
    if program == "tick":
        assert " while(" not in text
    z = GRANITE
    shapes = [f"bf16[{z['held']},{z['hidden']},{2 * z['width']}]",
              f"bf16[{z['held']},{z['width']},{z['hidden']}]",
              f"bf16[{z['vocab']},{z['hidden']}]",
              f"bf16[{z['hidden']},{z['vocab']}]",
              f"f32[{z['vocab']},{z['hidden']}]"]
    names = {"bfloat16": "bf16", "float32": "f32"}
    for layer in pool.buffers:
        shapes += [
            f"{names[buf.dtype.name]}[{','.join(map(str, buf.shape))}]"
            for key, buf in layer.items() if key != "conv"]
    assert "f32[32,64,128,128]" in shapes
    assert "bf16[1400,8,256,256]" in shapes
    made = [ln.strip()[:120] for ln in lines
            if any(f"= {sh}" in ln for sh in shapes)
            and (" copy(" in ln or " convert(" in ln or " transpose(" in ln)]
    assert not made, made
    memory = compiled[program].memory_analysis()
    assert memory.temp_size_in_bytes < {"tick": 256, "riding": 2048}[
        program] << 20, memory.temp_size_in_bytes
    # the states and the pages are donated and updated in place
    assert memory.alias_size_in_bytes > (9 * 32 * 64 * 128 * 128 * 4
                                         + 1400 * (1 << 20))


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
