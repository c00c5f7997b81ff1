"""Compile-only guards for the chip: the kernels and step programs of the
main path, at the widths chip_smoke.py serves, handed to the TPU compiler
for a DESCRIBED v5e:2x2 topology (no chip attached, nothing runs).

What interpret mode and the CPU mesh cannot see — a Mosaic tiling refusal,
a program that does not partition, a donation the TPU compiler rejects —
fails here at no chip time. A compile that passes is not a chip run.
"""

from __future__ import annotations

import functools
import json
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

import chip_smoke


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    # a compile for a described device is written to the persistent cache but
    # cannot be read back without a chip (JAX warns and recompiles): keep it off
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _smoke_decoder():
    """The generative geometry chip_smoke.py serves: decoder params plus the
    scheduler sizes of the example CR it boots."""
    from seldon_core_tpu.models.decoder import init_decoder

    g = chip_smoke.REAL_GEOMETRY
    gp = g["gen_params"]
    with open(
        os.path.join(chip_smoke.DEPLOYMENTS, "tiny_gpt_tensor_parallel.json")
    ) as f:
        tpu = json.load(f)["spec"]["predictors"][0]["tpu"]
    params = init_decoder(
        0, vocab=g["gen_vocab"], hidden=gp["hidden"], layers=gp["layers"],
        ffn=gp["ffn"], max_len=gp["max_len"],
    )
    ps = tpu["decode_kv_page_size"]
    return params, {
        "n_slots": tpu["decode_slots"],
        "n_pages": tpu["decode_kv_pages"],
        "page_size": ps,
        "pages_per_slot": -(-(gp["seq"] + gp["max_new_tokens"]) // ps),
    }


def _step_args(params, geo, kv_dtype, param_sh, pool_sh_for, small_sh):
    """ShapeDtypeStructs for models/decoder.py ``_fused_step`` — shapes only:
    there is no device to hold an array."""
    from seldon_core_tpu.models.decoder import paged_kv_init

    n = geo["n_slots"]
    p = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), params, param_sh
    )
    pool = tuple(
        jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=pool_sh_for(s))
        for s in jax.eval_shape(
            lambda: paged_kv_init(
                params, geo["n_pages"], geo["page_size"], kv_dtype=kv_dtype
            )
        )
    )

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=small_sh)

    i32, f32 = jnp.int32, jnp.float32
    return p, pool, (
        arr((n, geo["pages_per_slot"]), i32),  # block tables
        arr((n,), i32), arr((n,), i32),  # tokens, positions
        arr((n,), f32), arr((n,), i32),  # temperatures, top-k
        arr((), i32), arr((), i32),  # seed, tick
    )


def _pool_sized_entry_ops(text: str, pool) -> list[str]:
    """Opcodes of the entry computation's instructions whose result is as
    large as one pool component, or as one layer of it."""
    entry = re.search(r"ENTRY [^{]*\{(.*?)\n\}", text, re.S).group(1)
    ops = [
        (op, int(np.prod([int(d) for d in dims.split(",") if d])))
        for dims, op in re.findall(r"= \w+\[([\d,]*)\]\S* ([\w-]+)\(", entry)
    ]
    pool_elems = int(np.prod(pool[0].shape))
    return [op for op, n in ops if n in (pool_elems, pool_elems // pool[0].shape[0])]


def _pool_scatters(text: str, pool) -> list[tuple[int, str]]:
    """(indices, granule) of every scatter into an array shaped like a float
    pool component, anywhere in the program's text: the granule is "row"
    where the updates' window is one token row ``[w]``, "page" where it is a
    whole page ``[ps, w]``. Each must carry the ``kv_write`` scope."""
    hlo = {"float32": "f32", "bfloat16": "bf16"}
    # as the pool is, without the layer axis of a one-layer pool (the compiler drops it), and with
    # (layer, page) as one axis (the page form's own view of a plane)
    shapes = {"%s[%s]" % (hlo[a.dtype.name], ",".join(map(str, dims)))
              for a in pool for dims in (a.shape, a.shape[1:] if a.shape[0] == 1 else a.shape, (a.shape[0] * a.shape[1],) + a.shape[2:])}
    defs = dict(re.findall(r"%([\w.-]+) = s32\[(\d+)(?:,\d+)?\]", text))
    out = []
    for shape, idx, dims, name in re.findall(
        r"= (\w+\[[\d,]+\])\S* scatter\(%[\w.-]+, %([\w.-]+), [^)]*\), update_window_dims=\{([\d,]*)\}.*?op_name=\"([^\"]*)\"", text
    ):
        if shape in shapes:
            assert "/kv_write/" in name, name
            out.append((int(defs[idx]), {1: "row", 2: "page"}[len(dims.split(","))]))
    # and so must the fusion that holds it: a trace names an op by its fusion's metadata (the compiler's
    # own rewrite of a scatter of 1024 indices or more at (layer, page) left its fusion without any)
    for shape, rest in re.findall(r"= (\w+\[[\d,]+\])\S* fusion\(([^\n]*)", text):
        assert shape not in shapes or re.search(r'op_name="[^"]*/(kv_write|kv_gather|attn)/', rest), rest[:200]
    return out


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_compiles_at_bert_base_long_context(topo, causal):
    from seldon_core_tpu.ops.attention import PALLAS_MIN_SEQ
    from seldon_core_tpu.ops.pallas_flash import flash_attention

    q = jax.ShapeDtypeStruct(
        (1, 12, PALLAS_MIN_SEQ, 64), jnp.bfloat16,
        sharding=SingleDeviceSharding(topo.devices[0]),
    )
    compiled = (
        jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=causal))
        .lower(q, q, q)
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()  # Mosaic, not the interpreter


def test_fused_paged_decode_step_compiles_on_the_int8_pool(topo):
    from seldon_core_tpu.models.decoder import _fused_step

    one = SingleDeviceSharding(topo.devices[0])
    params, geo = _smoke_decoder()
    p, pool, rest = _step_args(
        params, geo, "int8", jax.tree.map(lambda _: one, params), lambda s: one, one
    )
    compiled = jax.jit(_fused_step, donate_argnums=(1,)).lower(p, pool, *rest).compile()
    # the donated pool comes back in place: the step allocates no second pool
    # (>=: the TPU layout pads the pool's minor dims to its tile)
    pool_bytes = sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in pool)
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes


def test_fused_step_writes_the_donated_pool_in_place_at_gpt2_large_geometry(topo):
    """The benchmark cell's pool geometry (gpt2-large: 20 heads of 64, 720
    pages of 16, 16 slots of 44 pages, float32; 8 of its 36 layers, shapes
    only): the step's per-layer scatter lands in the donated pool itself.
    The head-major layout this replaced compiled to a layout copy of one
    layer's pool before every scatter and a restack of the whole pool —
    three pools' worth of temporaries. Eight layers, not four: the gathered
    virtual cache of one layer (16 slots x 704 positions x 1280 x K and V,
    0.11 GiB) is the floor of the temporaries whatever the depth, and only
    from eight layers up is a quarter of the pool above it."""
    from seldon_core_tpu.models.decoder import init_decoder
    from seldon_core_tpu.models.decoder import _fused_step

    one = SingleDeviceSharding(topo.devices[0])
    params = jax.eval_shape(
        lambda: init_decoder(0, vocab=50257, hidden=1280, layers=8, ffn=5120, max_len=1024)
    )
    geo = {"n_slots": 16, "n_pages": 720, "page_size": 16, "pages_per_slot": 44}
    p, pool, rest = _step_args(
        params, geo, "", jax.tree.map(lambda _: one, params), lambda s: one, one
    )
    compiled = jax.jit(_fused_step, donate_argnums=(1,)).lower(p, pool, *rest).compile()
    pool_bytes = sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in pool)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes / 4
    # no copy of the pool, or of one layer of it, in the entry computation
    sized = _pool_sized_entry_ops(compiled.as_text(), pool)
    assert "fusion" in sized  # the scatters' own fusions: the pattern reads the text
    assert not [op for op in sized if op.startswith("copy")], sized
    # what the step writes is what it wrote before the chunks took pages (PR 40): one row a slot at
    # (layer, page, row), K and V of each layer, and no page read back to merge
    assert _pool_scatters(compiled.as_text(), pool) == [(16, "row")] * 16
    assert "/kv_write/gather" not in compiled.as_text()


def test_compact_chunk_writes_the_donated_pool_in_place_at_gpt2_large_geometry(topo):
    """The same cell's 256-token prefill chunk at the compact width (two
    rows of the scheduler's chunk ladder, block-table rows of two slots;
    8 of 36 layers, shapes only): the per-layer scatter lands in the donated
    pool, a page an index, and the temporaries are the two rows' own — the
    16-row program's gathered caches and scores come to 1.5 GiB here, these
    to 0.11."""
    from seldon_core_tpu.models.decoder import _fused_chunk, init_decoder

    one = SingleDeviceSharding(topo.devices[0])
    params = jax.eval_shape(
        lambda: init_decoder(0, vocab=50257, hidden=1280, layers=8, ffn=5120, max_len=1024)
    )
    geo = {"n_slots": 2, "n_pages": 720, "page_size": 16, "pages_per_slot": 44}
    p, pool, (bt, _tokens, pos, temps, topks, seed, tick) = _step_args(
        params, geo, "", jax.tree.map(lambda _: one, params), lambda s: one, one
    )
    ids = jax.ShapeDtypeStruct((2, 256), jnp.int32, sharding=one)
    compiled = (
        jax.jit(_fused_chunk, donate_argnums=(1,))
        .lower(p, pool, bt, ids, pos, pos, temps, topks, seed, tick)
        .compile()
    )
    pool_bytes = sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in pool)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes / 4
    sized = _pool_sized_entry_ops(compiled.as_text(), pool)
    assert not [op for op in sized if op.startswith("copy")], sized
    # the write goes by pages (PR 40): 2 rows x the 17 pages that 256 positions from any row of a page
    # can touch, whole [16, 1280] windows at (layer, page); no scatter is left with an index a token row
    assert _pool_scatters(compiled.as_text(), pool) == [(2 * 17, "page")] * 16  # none of 2 * 256 indices
    # the pages read back to merge are each row's first and last, not the seventeen
    assert re.findall(r"= f32\[(\d+),16,1280\]\S* gather\([^\n]*/kv_write/", compiled.as_text()) == ["4"] * 16


def test_fused_step_with_the_paged_attention_kernel_at_gpt2_large_geometry(topo):
    """The same cell geometry with the step's attention in the Pallas decode
    kernel (ops/paged_attention.py; ``attn_kernel`` is the static argument the
    program set's ``_step_attn_kernel`` answers on a TPU): Mosaic takes the
    kernel at the real widths, the kernel takes the WHOLE donated pool — which
    still aliases, with no copy of it or of one layer of it — and the gathered
    float32 cache (0.11 GiB) is gone from the temporaries."""
    from seldon_core_tpu.models.decoder import init_decoder
    from seldon_core_tpu.models.decoder import gpt2_family

    one = SingleDeviceSharding(topo.devices[0])
    params = jax.eval_shape(
        lambda: init_decoder(0, vocab=50257, hidden=1280, layers=8, ffn=5120, max_len=1024)
    )
    geo = {"n_slots": 16, "n_pages": 720, "page_size": 16, "pages_per_slot": 44}
    p, pool, rest = _step_args(
        params, geo, "", jax.tree.map(lambda _: one, params), lambda s: one, one
    )
    step, _chunk = gpt2_family.fused_programs("mosaic")
    compiled = jax.jit(step, donate_argnums=(1,)).lower(p, pool, *rest).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 8  # a kernel call a layer: Mosaic, not the interpreter
    pool_bytes = sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in pool)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    # under the gather path's floor, one layer's K and V virtual caches
    # (that step compiles to 0.142 GiB of temporaries, this one to 0.012)
    assert mem.temp_size_in_bytes < (2 * 16 * 704 * 1280 * 4) // 4
    sized = _pool_sized_entry_ops(text, pool)
    assert "fusion" in sized  # the scatters' own fusions
    assert not [op for op in sized if op.startswith("copy")], sized
    # what is left of the gather keeps its scope, and the kernel lies under attn
    assert re.search(r'op_name="jit\(_fused_step\)/kv_gather/', text)
    assert re.search(r'custom_call_target="tpu_custom_call"[^\n]*op_name="jit\(_fused_step\)/attn/', text)


def _without_locations(text: str) -> str:
    """Lowered text with each Mosaic kernel's serialized module (which
    carries source paths and line numbers) replaced by the hash of its
    location-free form."""
    import base64
    import hashlib
    import json

    from jax._src.lib.mlir import ir

    def kernel(m):
        body = json.loads(m.group(1).replace("\\22", '"'))["custom_call_config"]["body"]
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            asm = ir.Module.parse(base64.b64decode(body)).operation.get_asm(enable_debug_info=False)
        return 'backend_config = "mosaic:%s"' % hashlib.sha256(asm.encode()).hexdigest()

    return re.sub(r'backend_config = "(\{[^\n]*?\})"', kernel, text)


def test_gpt2_large_kernel_step_lowers_to_the_text_it_had(topo):
    """The GPT-2 family's step with ITS kernel (ops/paged_attention.py) at
    gpt2-large's widths, two layers: what it lowers to for the chip is what
    it lowered to before the grouped-query kernel came (PR 42's parent,
    f11f215, hashed there with this helper): the program, and the Mosaic
    module inside it, source locations aside."""
    import hashlib

    from seldon_core_tpu.models.decoder import gpt2_family, init_decoder

    one = SingleDeviceSharding(topo.devices[0])
    params = jax.eval_shape(lambda: init_decoder(0, vocab=50257, hidden=1280, layers=2, ffn=5120, max_len=1024))
    geo = {"n_slots": 16, "n_pages": 720, "page_size": 16, "pages_per_slot": 44}
    p, pool, rest = _step_args(params, geo, "", jax.tree.map(lambda _: one, params), lambda s: one, one)
    step, _chunk = gpt2_family.fused_programs("mosaic")
    text = _without_locations(jax.jit(step, donate_argnums=(1,)).lower(p, pool, *rest).as_text())
    assert text.count('"mosaic:') == 1  # the layers' calls lower the kernel once
    assert hashlib.sha256(text.encode()).hexdigest() == "7118908d312842cc732bc217c04879ef2ece1c3926277df2f2f3699d2995fea9"


@pytest.mark.parametrize(
    "pool_kind, width, page_size, want",
    [
        ("kv", 1280, 16, "mosaic"),  # the gpt2-large cells
        ("kv", 256, 8, "mosaic"),  # chip_smoke's width, the smallest page Mosaic takes
        ("kv", 1600, 16, ""),  # gpt2-xl: 25 heads of 64, a row that is not whole 128-lane tiles
        ("kv", 1280, 4, ""),  # a page under one sublane tile
        ("latent", 640, 16, "mosaic"),  # the a.x-k1 cell: 512 + 64 in five lane tiles
        ("latent", 256, 32, "mosaic"),  # a latent of one lane tile, two sublane tiles a page
        ("latent", 576, 16, ""),  # the published row as it is: not whole lane tiles
        ("latent", 640, 8, ""),  # a page under a two-byte float's sublane tile
        ("gqa", 512, 16, "mosaic"),  # the lfm2-24b-a2b and granite-4.0-h-micro cells: 32 / 8 heads of 64, bfloat16
        ("gqa", 1024, 32, "mosaic"),  # 8 K/V heads of 128
        ("gqa", 512, 4, ""),  # a page under a two-byte float's sublane tile
        ("gqa_float32", 512, 16, ""),  # grouped heads over a four-byte pool: neither kernel's
        ("gqa_int8", 512, 16, ""),  # the int8 pool's six components
        ("gqa_mesh", 512, 16, ""),  # a decode mesh
        ("two_kinds", 1024, 16, "mosaic"),  # the laguna-s-2.1 cell: 48 / 72 query heads over 8 K/V heads of 128, two page kinds
        ("two_kinds", 512, 16, "mosaic"),  # the mellum2-12b-a2.5b cell's rows (heads of 64 here)
        ("two_kinds", 1024, 8, ""),  # a page under a two-byte float's sublane tile, in both kinds
        ("two_kinds_one_float32", 1024, 16, ""),  # one kind's planes four bytes wide: the other alone cannot take the step
        ("two_kinds_one_ragged", 1024, 16, ""),  # the sliding layers' 44 query heads are 5.5 to a K/V head
        ("two_kinds_int8", 1024, 16, ""),  # the int8 pool: six planes a kind
        ("two_kinds_mesh", 1024, 16, ""),  # a decode mesh
    ],
)
def test_step_attn_kernel_is_chosen_only_where_mosaic_tiles_it(topo, pool_kind, width, page_size, want):
    """``_step_attn_kernel`` on a described TPU: the kernel where Mosaic can
    tile the pool's rows and pages, the gather path (the latent family: the
    walk) where it cannot — and what it chose compiles either way. Asking
    for the kernel at a refused geometry is a named error before Mosaic sees
    it. ``kv``: the GPT-2 family's two-component pool of ``width`` = hidden
    and the whole step; ``latent``: the latent family's one bfloat16 plane of
    ``width`` lanes and its kernel alone (the whole step at the cell's widths
    is ``test_latent_family_programs_compile_in_place_at_a_x_k1_widths``)."""
    from seldon_core_tpu.models.decoder import decoder_dims, init_decoder
    from seldon_core_tpu.models.decoder import gpt2_family
    from seldon_core_tpu.serving.decode_programs import _step_attn_kernel

    one = SingleDeviceSharding(topo.devices[0])
    if pool_kind == "latent":
        from seldon_core_tpu.models import mla_decoder as mla
        from seldon_core_tpu.ops.mla import mla_decode_attention, page_runs

        def arr(shape, dt):
            return jax.ShapeDtypeStruct(shape, dt, sharding=one)

        plane = arr((2, 256, page_size, width), jnp.bfloat16)
        assert _step_attn_kernel(mla.mla_family(mla.MLADecoderConfig()), (plane,), None, 16, 1) == want

        def attend(qc, plane, bt, n_keys):
            runs = page_runs(bt, n_keys, page_size)
            return mla_decode_attention(qc, plane, 1, bt, n_keys, runs, rank=width - 128, scale=0.1)

        shapes = (arr((4, 16, width), jnp.bfloat16), plane, arr((4, 40), jnp.int32), arr((4,), jnp.int32))
        if want:
            assert "tpu_custom_call" in jax.jit(attend).lower(*shapes).compile().as_text()
        else:
            with pytest.raises(ValueError, match="kernel_tiles"):
                jax.jit(attend).lower(*shapes)
        return
    if pool_kind.startswith("two_kinds"):
        # the sparse-expert family's pool of two page kinds: the full layers' planes, then the sliding layers' (fewer
        # pages, more layers), asked once a kind with that kind's query heads; the sub-table's kernel alone compiles
        # (the whole step at the cell's widths is ``test_the_two_page_kinds_programs_compile_...`` below)
        from seldon_core_tpu.models import moe_decoder as md
        from seldon_core_tpu.ops.gqa_decode import gqa_decode_attention, step_reads

        def arr(shape, dt):
            return jax.ShapeDtypeStruct(shape, dt, sharding=one)

        fam = md.moe_family(md.MoEDecoderConfig())
        assert fam.cfg.two_kinds and "attn_kernel" in fam.serves
        full_dt = jnp.float32 if pool_kind == "two_kinds_one_float32" else jnp.bfloat16
        pool = (arr((1, 256, page_size, width), full_dt),) * 2 + (arr((3, 128, page_size, width), jnp.bfloat16),) * 2
        if pool_kind == "two_kinds_int8":
            kind = lambda layers, pages: (  # noqa: E731
                arr((layers, pages, page_size, width), jnp.int8), *(arr((layers, pages, page_size), jnp.float32),) * 2) * 2
            pool = kind(1, 256) + kind(3, 128)
        mesh = Mesh(np.asarray(topo.devices[:2]), ("model",)) if pool_kind == "two_kinds_mesh" else None
        heads_window = 44 if pool_kind == "two_kinds_one_ragged" else 72
        assert _step_attn_kernel(fam, pool, mesh, 48, 8, heads_window) == want
        if pool_kind != "two_kinds" or not want:
            return

        def attend(q, pk, pv, bt, positions, k0, rows):
            reads = step_reads(bt, positions, rows, page_size, k0, 512)
            return gqa_decode_attention(q, pk, pv, 2, bt, *reads, scale=0.1)

        shapes = (arr((4, 72, width // 8), jnp.bfloat16), *pool[2:], arr((4, 34), jnp.int32), arr((4,), jnp.int32),
                  arr((4,), jnp.int32), arr((4,), jnp.bool_))
        assert "tpu_custom_call" in jax.jit(attend).lower(*shapes).compile().as_text()
        return
    if pool_kind.startswith("gqa"):
        # a two-plane pool whose 8 K/V heads serve 32 query heads: ops/gqa_decode.py's kernel alone (the whole steps
        # at the cells' widths are the two families' own cases below)
        from seldon_core_tpu.models import conv_decoder as cd
        from seldon_core_tpu.ops.gqa_decode import gqa_decode_attention, step_reads

        def arr(shape, dt):
            return jax.ShapeDtypeStruct(shape, dt, sharding=one)

        fam = cd.conv_family(cd.ConvDecoderConfig())
        dt = jnp.float32 if pool_kind == "gqa_float32" else jnp.bfloat16
        pool = (arr((2, 256, page_size, width), dt),) * 2
        if pool_kind == "gqa_int8":
            pool = (arr((2, 256, page_size, width), jnp.int8), *(arr((2, 256, page_size), jnp.float32),) * 2) * 2
        mesh = Mesh(np.asarray(topo.devices[:2]), ("model",)) if pool_kind == "gqa_mesh" else None
        assert _step_attn_kernel(fam, pool, mesh, 32, 8) == want
        if pool_kind != "gqa":
            return

        def attend(q, pk, pv, bt, positions, rows):
            return gqa_decode_attention(q, pk, pv, 1, bt, *step_reads(bt, positions, rows, page_size), scale=0.125)

        shapes = (arr((4, 32, width // 8), jnp.bfloat16), *pool, arr((4, 40), jnp.int32), arr((4,), jnp.int32),
                  arr((4,), jnp.bool_))
        if want:
            assert "tpu_custom_call" in jax.jit(attend).lower(*shapes).compile().as_text()
        else:
            with pytest.raises(ValueError, match="gqa_tiles"):
                jax.jit(attend).lower(*shapes)
        return
    hidden = width
    params = jax.eval_shape(
        lambda: init_decoder(0, vocab=1024, hidden=hidden, layers=2, ffn=4 * hidden, max_len=256)
    )
    geo = {"n_slots": 4, "n_pages": 64, "page_size": page_size, "pages_per_slot": 96 // page_size}
    p, pool, rest = _step_args(
        params, geo, "", jax.tree.map(lambda _: one, params), lambda s: one, one
    )
    dims = decoder_dims(params)
    got = _step_attn_kernel(gpt2_family, pool, None, dims["heads"], dims["kv_heads"])
    assert got == want
    step, _chunk = gpt2_family.fused_programs(got)
    text = jax.jit(step, donate_argnums=(1,)).lower(p, pool, *rest).compile().as_text()
    assert ("tpu_custom_call" in text) == bool(want)
    if not want:
        forced, _chunk = gpt2_family.fused_programs("mosaic")
        with pytest.raises(ValueError, match="mosaic_tiles"):
            jax.jit(forced, donate_argnums=(1,)).lower(p, pool, *rest)


def test_tp4_sharded_decode_step_compiles(topo):
    from seldon_core_tpu.parallel.tp import decoder_param_shardings, kv_sharding
    from seldon_core_tpu.models.decoder import _fused_step

    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("tp",))
    rep = NamedSharding(mesh, P())
    params, geo = _smoke_decoder()
    p, pool, rest = _step_args(
        params, geo, "", decoder_param_shardings(params, mesh, "tp"),
        lambda s: kv_sharding(mesh, "tp", s), rep,
    )
    pool_sh = tuple(x.sharding for x in pool)
    compiled = (
        jax.jit(_fused_step, donate_argnums=(1,), out_shardings=(rep, pool_sh))
        .lower(p, pool, *rest)
        .compile()
    )
    # Megatron/Pope: each residual branch ends in an all-reduce
    assert "all-reduce" in compiled.as_text()
    # the page pool is head-sharded: a device holds a quarter of every token
    # row (in the TPU's (8, 128) float32 tiling, which pads the smoke
    # geometry's one-head shard of 64 lanes to 128)
    shard_w = pool[0].shape[-1] // 4
    padded_w = -(-shard_w // 128) * 128
    per_device = sum(
        int(np.prod(s.shape[:-1])) * padded_w * s.dtype.itemsize for s in pool
    )
    assert compiled.memory_analysis().alias_size_in_bytes == per_device


@pytest.mark.parametrize("rows", [1024, 4096], ids=["chunk64", "chunk256"])
def test_grouped_expert_layer_compiles_with_the_pallas_kernel_at_published_widths(topo, rows, monkeypatch):
    """The sparse-expert family's chunk rounds (ops/moe.py) at the
    mellum2-12b-a2.5b cell's widths: 16 slots x 64 / 256 tokens routed top-8
    of 64 experts of 896 over hidden 2304. On a TPU the grouped products are
    the megablox Pallas kernel (``_grouped_dot``); the CPU tests run
    ``lax.ragged_dot`` and cannot see a tiling the chip's compiler refuses."""
    from seldon_core_tpu.ops import moe

    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    bf = jnp.bfloat16
    p = {"router": sds((2304, 64), bf), "gate_up": sds((64, 2304, 1792), bf), "down": sds((64, 896, 2304), bf)}
    compiled = (
        jax.jit(lambda p, x, valid: moe.moe_topk_ffn(p, x, 8, valid))
        .lower(p, sds((rows, 2304), bf), sds((rows,), jnp.bool_))
        .compile()
    )
    assert rows > moe.MASKED_MAX_ROWS  # the grouped form, not the masked one
    assert compiled.as_text().count("tpu_custom_call") >= 2  # gate_up and down


def _grouped_product_rows(text: str) -> list[int]:
    """The row counts of a compiled program's grouped expert products (the
    megablox kernel's calls: ``%gmm.N = dtype[rows, n] custom-call(...)``),
    in the text's order."""
    return [int(m) for m in re.findall(r"%gmm[.\d]* = \w+\[(\d+),\d+\][^\n]*custom_call_target=\"tpu_custom_call\"", text)]


@pytest.mark.parametrize(
    "cell, rows, hidden, ffn, held, routed, k, cap",
    [("laguna-s-2.1", 1024, 3072, 1024, 32, 256, 10, 2816), ("lfm2-24b-a2b", 1024, 2048, 1536, 8, 64, 4, 1280),
     ("lfm2-24b-a2b", 512, 2048, 1536, 8, 64, 4, 768), ("xing4.0-29b-a4b", 1024, 3584, 1024, 8, 64, 4, 1280)],
    ids=["laguna_4_256", "lfm2_4_256", "lfm2_2_256", "xing_4_256"],
)
def test_held_expert_layer_compiles_compact_at_the_three_cells_widths(
        topo, monkeypatch, cell, rows, hidden, ffn, held, routed, k, cap):
    """``moe_held_ffn`` over a 256-token chunk entry's rows at the widths of the
    three cells that hold a share of their experts (PR 49): the blocks' loop
    compiles for the chip, its two grouped products over ``held_capacity``
    rows (2,816 of laguna's 10,240 assignments), and nothing as long as the
    assignments goes through a product or comes back in float32: no branch,
    no full-width form beside it."""
    from seldon_core_tpu.ops import moe

    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    bf = jnp.bfloat16
    p = {"router": sds((hidden, routed), bf), "gate_up": sds((held, hidden, 2 * ffn), bf), "down": sds((held, ffn, hidden), bf)}
    compiled = (
        jax.jit(lambda p, x, g, e, v: moe.moe_held_ffn(p, x, g, e, held, v))
        .lower(p, sds((rows, hidden), bf), sds((rows, k), jnp.float32), sds((rows, k), jnp.int32), sds((rows,), jnp.bool_))
        .compile()
    )
    assert moe.held_capacity(rows * k, held, routed) == cap < rows * k
    text = compiled.as_text()
    assert _grouped_product_rows(text) == [cap, cap]
    assert " conditional(" not in text and "f32[%d,%d]" % (rows * k, hidden) not in text


def _assert_step_reads_the_pool_through_the_kernel(text: str, layers: int):
    """A grouped-query family's step with ops/gqa_decode.py's kernel: a
    Mosaic call an attention layer under ``attn``, what is left of the
    gather (lengths, run flags) under ``kv_gather``, and no gathered
    float32 cache."""
    calls = re.findall(r'custom_call_target="tpu_custom_call"[^\n]*op_name="jit\(_fused_step\)/attn/', text)
    assert len(calls) == layers
    assert re.search(r'op_name="jit\(_fused_step\)/kv_gather/', text)
    assert not re.search(r"f32\[64,\d+,16,512\]", text)  # [slots, table, page, row] upcast


@pytest.mark.parametrize("program", ["step", "step_kernel", "chunk_2_64"])
def test_hybrid_family_updates_state_rows_in_place_at_granite_micro_widths(topo, program):
    """The third family's fused step (64 slots; through the gather, and with
    the grouped-query kernel as on a TPU) and (2, 64) chunk at the
    granite-4.0-h-micro cell's widths, 10 of its 40 layers (one period: 9
    Mamba-2 layers + 1 attention layer): the donated pool AND the donated
    state rows come back aliased, and no op of the program copies an array
    the size of the state (PR 27's finding, for the second cache)."""
    from seldon_core_tpu.models import hybrid_decoder as hd

    one = SingleDeviceSharding(topo.devices[0])
    cfg = hd.HybridDecoderConfig(
        vocab=100352, hidden=2048, layers=10, attn_layers=(5,), heads=32, kv_heads=8, head_dim=64, ffn=8192,
        ssm_heads=64, ssm_head_dim=64, ssm_state=128, attention_multiplier=0.015625,
    )
    fam = hd.hybrid_family(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), tree)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = on_chip(jax.eval_shape(lambda: hd.init_hybrid_decoder(cfg, 0, jnp.bfloat16)))
    n, rows_total = 64, 64 + 4 + 1
    pool = on_chip(jax.eval_shape(lambda: fam.paged_kv_init(params, 3400, 16, jnp.bfloat16)))
    rec = on_chip(jax.eval_shape(lambda: fam.state_init(params, rows_total)))
    assert pool[0].shape[0] == 1 and len(rec) == 18 and rec[0].shape == (rows_total, 64, 64, 128)
    step, chunk = fam.fused_programs("mosaic" if program == "step_kernel" else "")
    i32, f32 = jnp.int32, jnp.float32
    if program.startswith("step"):
        args = (arr((n, 52), i32), arr((n,), i32), arr((n,), i32), arr((n,), f32), arr((n,), i32), arr((), i32),
                arr((), i32), arr((n,), jnp.bool_))
        fn = step
    else:
        r, c = 2, 64
        args = (arr((r, 52), i32), arr((r, c), i32), arr((r,), i32), arr((r,), i32), arr((r,), f32),
                arr((r,), i32), arr((), i32), arr((), i32), arr((3, r), i32))
        fn = chunk
    compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(params, pool, rec, *args).compile()
    donated = sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in (*pool, *rec))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= donated
    assert mem.temp_size_in_bytes < donated // 4  # nothing the size of the state beside it
    state = re.escape("f32[%d,64,64,128]" % rows_total)
    copies = [ln for ln in compiled.as_text().splitlines() if re.search(r"= " + state + r"\S* copy\(", ln)]
    assert not copies, copies[:2]
    assert re.search(r'op_name="jit\(_fused_%s\)/attn/ssm_scan/' % ("chunk" if program == "chunk_2_64" else "step"), compiled.as_text())
    # the attention layer's K and V: a row a slot in the step, 2 x 5 whole pages in the (2, 64) chunk (PR 40)
    want = (2 * 5, "page") if program == "chunk_2_64" else (64, "row")
    assert _pool_scatters(compiled.as_text(), pool) == [want] * 2
    if program == "step_kernel":
        _assert_step_reads_the_pool_through_the_kernel(compiled.as_text(), 1)


@pytest.mark.parametrize("program", ["step", "step_kernel", "chunk_4_256"])
def test_hybrid_family_with_single_sublayers_compiles_in_place_at_nemotron_nano_widths(topo, program, monkeypatch):
    """The hybrid family's other shape (PR 51) at the
    nemotron-3-nano-30b-a3b cell's widths, the pattern's first seven layers
    (3 Mamba-2 with eight B/C groups, 3 expert layers that hold 16 of 128
    squared-ReLU experts of 1856 stored as 1920, 1 attention layer of 32 / 2
    heads of 128): the fused step (64 slots; through the gather, and with the
    grouped-query kernel at a group of SIXTEEN over a 256-lane row) and the
    (4, 256) chunk compile for the chip; pool and state rows come back
    aliased and nothing the size of the state is copied; the chunk's expert
    layers run the megablox kernel over ``held_capacity`` rows and no
    ``ragged-dot`` (the width off the tile is stored in whole tiles)."""
    from seldon_core_tpu.models import hybrid_decoder as hd
    from seldon_core_tpu.ops import moe
    from seldon_core_tpu.ops.gqa_decode import gqa_tiles

    assert gqa_tiles(256, 32, 2, 16, jnp.bfloat16) and not gqa_tiles(256, 32, 2, 16, jnp.float32)
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    one = SingleDeviceSharding(topo.devices[0])
    cfg = hd.HybridDecoderConfig(
        vocab=16384, hidden=2688, layers=7, pattern="MEMEM*E", heads=32, kv_heads=2, head_dim=128, ffn=1856,
        ssm_heads=64, ssm_head_dim=64, ssm_state=128, ssm_groups=8, untied=True, experts=128, experts_held=16,
        experts_per_tok=6, shared_ffn=3712, routed_scale=2.5, embedding_multiplier=1.0, residual_multiplier=1.0,
        attention_multiplier=128**-0.5, logits_scaling=1.0, max_len=262144,
    )
    fam = hd.hybrid_family(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), tree)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = on_chip(jax.eval_shape(lambda: hd.init_hybrid_decoder(cfg, 0, jnp.bfloat16)))
    assert params["layers"][1]["moe"]["up"].shape == (16, 2688, 1920) and params["layers"][1]["shared"]["up"].shape == (2688, 3712)
    n, rows_total = 64, 64 + 4 + 1
    pool = on_chip(jax.eval_shape(lambda: fam.paged_kv_init(params, 9400, 16, jnp.bfloat16)))
    rec = on_chip(jax.eval_shape(lambda: fam.state_init(params, rows_total)))
    assert pool[0].shape == (1, 9400, 16, 256) and len(rec) == 6
    assert rec[0].shape == (rows_total, 64, 64, 128) and rec[3].shape == (rows_total, 3 * 6144)
    step, chunk = fam.fused_programs("mosaic" if program == "step_kernel" else "")
    i32, f32 = jnp.int32, jnp.float32
    if program.startswith("step"):
        args = (arr((n, 144), i32), arr((n,), i32), arr((n,), i32), arr((n,), f32), arr((n,), i32), arr((), i32),
                arr((), i32), arr((n,), jnp.bool_))
        fn = step
    else:
        r, c = 4, 256
        args = (arr((r, 144), i32), arr((r, c), i32), arr((r,), i32), arr((r,), i32), arr((r,), f32),
                arr((r,), i32), arr((), i32), arr((), i32), arr((3, r), i32))
        fn = chunk
    compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(params, pool, rec, *args).compile()
    donated = sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in (*pool, *rec))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= donated
    text = compiled.as_text()
    state = re.escape("f32[%d,64,64,128]" % rows_total)
    copies = [ln for ln in text.splitlines() if re.search(r"= " + state + r"\S* copy\(", ln)]
    assert not copies, copies[:2]
    kind = "chunk" if program == "chunk_4_256" else "step"
    for outer, inner in (("attn", "ssm_scan"), ("mlp", "shared_expert"), ("mlp", "moe_experts"), ("mlp", "moe_router")):
        # the compact form's products lie in its blocks' loop: ``mlp/while/body/moe_experts``
        assert re.search(r'op_name="jit\(_fused_%s\)/%s/([^"/]+/)*%s/' % (kind, outer, inner), text), inner
    assert "ragged-dot" not in text
    if program == "chunk_4_256":
        cap = moe.held_capacity(4 * 256 * 6, 16, 128)
        assert cap == 1792 and _grouped_product_rows(text) == [cap, cap] * 3  # up then down, an expert layer
    else:
        assert _grouped_product_rows(text) == []  # 64 rows: the masked form
    if program == "step_kernel":
        _assert_step_reads_the_pool_through_the_kernel(text, 1)


@pytest.mark.parametrize("program", ["step_kernel", "chunk_4_256_kernel"])
def test_hybrid_family_third_shape_compiles_in_place_at_qwen3_next_widths(topo, program, monkeypatch):
    """The hybrid family's third shape (PR 57) at the qwen3-next-80b-a3b
    cell's widths, one period of the pattern (3 gated delta-rule layers of
    16 / 32 heads of 128 with a [32, 128, 128] float32 matrix state a row, 1
    gated attention layer of 16 / 2 heads of 256, every one with an expert
    layer that holds 32 of 512 gated-SiLU experts of 512 and a gated shared
    one): the fused step of 64 slots and the (4, 256) chunk compile for the
    chip WITH the grouped-query kernels at a head of two lane tiles; pool and
    state rows come back aliased and nothing the size of the state is copied;
    the chunk's expert layers run the megablox kernel over ``held_capacity``
    rows and no ``ragged-dot``; the delta rule's scopes are in the text."""
    from seldon_core_tpu.models import hybrid_decoder as hd
    from seldon_core_tpu.ops import moe
    from seldon_core_tpu.ops.gqa_decode import gqa_chunk_tiles, gqa_tiles

    assert gqa_tiles(512, 16, 2, 16, jnp.bfloat16) and gqa_chunk_tiles("mosaic", 256, 16, 2, 256)
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    one = SingleDeviceSharding(topo.devices[0])
    cfg = hd.HybridDecoderConfig(
        vocab=18992, hidden=2048, layers=4, pattern="DDDG", heads=16, kv_heads=2, head_dim=256, ffn=512, untied=True,
        experts=512, experts_held=32, experts_per_tok=10, shared_ffn=512, gdn_key_heads=16, gdn_value_heads=32,
        gdn_key_dim=128, gdn_value_dim=128, rope_theta=1e7, rotary=0.25, embedding_multiplier=1.0,
        residual_multiplier=1.0, attention_multiplier=256**-0.5, logits_scaling=1.0, rms_eps=1e-6, max_len=262144,
    )
    fam = hd.hybrid_family(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), tree)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = on_chip(jax.eval_shape(lambda: hd.init_hybrid_decoder(cfg, 0, jnp.bfloat16)))
    assert params["layers"][0]["gdn_in"].shape == (2048, 12288) and params["layers"][3]["attn_qkv"].shape == (2048, 9216)
    assert params["layers"][0]["moe"]["gate_up"].shape == (32, 2048, 1024)
    n, rows_total = 64, 64 + 4 + 1
    pool = on_chip(jax.eval_shape(lambda: fam.paged_kv_init(params, 9400, 16, jnp.bfloat16)))
    rec = on_chip(jax.eval_shape(lambda: fam.state_init(params, rows_total)))
    assert pool[0].shape == (1, 9400, 16, 512) and len(rec) == 6
    assert rec[0].shape == (rows_total, 32, 128, 128) and rec[3].shape == (rows_total, 3 * 8192)
    step, chunk = fam.fused_programs("mosaic")
    i32, f32 = jnp.int32, jnp.float32
    if program == "step_kernel":
        args = (arr((n, 144), i32), arr((n,), i32), arr((n,), i32), arr((n,), f32), arr((n,), i32), arr((), i32),
                arr((), i32), arr((n,), jnp.bool_))
        fn = step
    else:
        r, c = 4, 256
        args = (arr((r, 144), i32), arr((r, c), i32), arr((r,), i32), arr((r,), i32), arr((r,), f32),
                arr((r,), i32), arr((), i32), arr((), i32), arr((3, r), i32))
        fn = chunk
    compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(params, pool, rec, *args).compile()
    donated = sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in (*pool, *rec))
    assert compiled.memory_analysis().alias_size_in_bytes >= donated
    text = compiled.as_text()
    state = re.escape("f32[%d,32,128,128]" % rows_total)
    copies = [ln for ln in text.splitlines() if re.search(r"= " + state + r"\S* copy\(", ln)]
    assert not copies, copies[:2]
    kind = "step" if program == "step_kernel" else "chunk"
    for outer, inner in (("qkv", "gdn_in"), ("attn", "gdn_conv"), ("attn", "gdn_scan"), ("attn_out", "gdn_norm"),
                         ("attn_out", "gdn_out"), ("qkv", "rope"), ("attn_out", "attn_gate"), ("mlp", "shared_expert"),
                         ("mlp", "moe_experts"), ("mlp", "moe_router")):
        assert re.search(r'op_name="jit\(_fused_%s\)/%s/([^"/]+/)*%s/' % (kind, outer, inner), text), inner
    assert "ragged-dot" not in text
    if kind == "chunk":
        cap = moe.held_capacity(4 * 256 * 10, 32, 512)
        assert cap == 1536 and _grouped_product_rows(text) == [cap, cap] * 4  # gate_up then down, an expert layer
        assert text.count("gqa_chunk_attention") >= 1
    else:
        assert _grouped_product_rows(text) == []  # 64 rows: the masked form
        _assert_step_reads_the_pool_through_the_kernel(text, 1)


@pytest.mark.parametrize("program", ["step", "chunk_2_256", "chunk_4_256"])
def test_hybrid_family_third_shape_compiles_with_the_delta_rule_kernels_at_qwen3_next_widths(topo, program, monkeypatch):
    """The third shape's programs as a TPU runs them (PR 58): where
    ``gated_delta.kernel_mode`` answers "mosaic" (16 / 32 heads of 128, float32
    state rows) the delta-rule layers' step and chunk are ops/gated_delta.py's
    kernels, one call a layer under ``attn/gdn_scan``, the ``[69, 32, 128,
    128]`` state arrays aliased in place: no copy, slice or
    dynamic-update-slice of one anywhere in the program, and Mosaic takes the
    kernels inside the VMEM they ask for."""
    from seldon_core_tpu.models import hybrid_decoder as hd
    from seldon_core_tpu.ops import gated_delta as gd
    from seldon_core_tpu.ops import moe

    assert gd._VMEM_LIMIT <= 100 << 20  # what the kernels may ask of the chip's 128 MiB
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    monkeypatch.setattr(hd, "gdn_kernel_mode", lambda dk, dv, dtype: "mosaic")  # ``kernel_mode``'s answer on the chip
    jax.clear_caches()  # the plain form's trace of the same programs is not this one's
    one = SingleDeviceSharding(topo.devices[0])
    cfg = hd.HybridDecoderConfig(
        vocab=18992, hidden=2048, layers=4, pattern="DDDG", heads=16, kv_heads=2, head_dim=256, ffn=512, untied=True,
        experts=512, experts_held=32, experts_per_tok=10, shared_ffn=512, gdn_key_heads=16, gdn_value_heads=32,
        gdn_key_dim=128, gdn_value_dim=128, rope_theta=1e7, rotary=0.25, embedding_multiplier=1.0,
        residual_multiplier=1.0, attention_multiplier=256**-0.5, logits_scaling=1.0, rms_eps=1e-6, max_len=262144,
    )
    fam = hd.hybrid_family(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), tree)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = on_chip(jax.eval_shape(lambda: hd.init_hybrid_decoder(cfg, 0, jnp.bfloat16)))
    n, rows_total = 64, 64 + 4 + 1
    pool = on_chip(jax.eval_shape(lambda: fam.paged_kv_init(params, 9400, 16, jnp.bfloat16)))
    rec = on_chip(jax.eval_shape(lambda: fam.state_init(params, rows_total)))
    assert rec[0].shape == (rows_total, 32, 128, 128) and rec[0].dtype == jnp.float32
    step, chunk = fam.fused_programs("mosaic")
    i32, f32 = jnp.int32, jnp.float32
    if program == "step":
        args = (arr((n, 144), i32), arr((n,), i32), arr((n,), i32), arr((n,), f32), arr((n,), i32), arr((), i32),
                arr((), i32), arr((n,), jnp.bool_))
        fn, kernel = step, "gdn_step"
    else:
        r, c = (int(x) for x in program.split("_")[1:])
        args = (arr((r, 144), i32), arr((r, c), i32), arr((r,), i32), arr((r,), i32), arr((r,), f32),
                arr((r,), i32), arr((), i32), arr((), i32), arr((3, r), i32))
        fn, kernel = chunk, "gdn_chunk"
    try:
        compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(params, pool, rec, *args).compile()
    finally:
        jax.clear_caches()
    donated = sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in (*pool, *rec))
    assert compiled.memory_analysis().alias_size_in_bytes >= donated
    text = compiled.as_text()
    state = re.escape("f32[%d,32,128,128]" % rows_total)
    moved = [ln for ln in text.splitlines() if re.search(r"= " + state + r"\S* (copy|slice|dynamic-slice|dynamic-update-slice|scatter|gather)\(", ln)]
    assert not moved, moved[:2]
    calls = [ln for ln in text.splitlines() if "custom-call" in ln and 'custom_call_target="tpu_custom_call"' in ln
             and re.search(r'op_name="jit\(_fused_%s\)/attn/([^"/]+/)*gdn_scan/' % ("step" if program == "step" else "chunk"), ln)]
    assert len(calls) == 3 and all(re.search(state, ln) for ln in calls), len(calls)  # a layer's array in, the same out
    assert text.count(kernel) >= 3


def _ungated_lines(text: str) -> list[str]:
    """The compiled module's instructions that run whatever a ``conditional``
    decides: the entry computation's and those of every computation it
    reaches other than as a ``conditional``'s branch (fusions, loop bodies,
    reducers)."""
    comps: dict[str, list[str]] = {}
    entry = name = None
    for ln in text.splitlines():
        m = re.match(r"(ENTRY )?%([\w.\-]+) \(.*\{$", ln)
        if m:
            name = m.group(2)
            comps[name] = []
            entry = name if m.group(1) else entry
        elif ln.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(ln)
    seen, todo = set(), [entry]
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for ln in comps[c]:
            if " conditional(" in ln:
                continue
            todo += re.findall(r"(?:calls|to_apply|body|condition)=%([\w.\-]+)", ln)
    return [ln for c in seen for ln in comps[c]]


def _family_step(family: str, one):
    """(step program, its arguments as shapes, donate_argnums, the donated
    arguments) of one decoder family at its benchmark cell's rows, widths
    and vocabulary, and a few of its layers."""
    i32, f32 = jnp.int32, jnp.float32

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), tree)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def small(n, m):  # block tables, tokens, positions, temperatures, top-k, seed, tick
        return (arr((n, m), i32), arr((n,), i32), arr((n,), i32), arr((n,), f32), arr((n,), i32),
                arr((), i32), arr((), i32))

    if family == "gpt2":
        from seldon_core_tpu.models.decoder import gpt2_family, init_decoder

        params = jax.eval_shape(lambda: init_decoder(0, vocab=50257, hidden=1280, layers=2, ffn=5120, max_len=1024))
        geo = {"n_slots": 16, "n_pages": 720, "page_size": 16, "pages_per_slot": 44}
        p, pool, rest = _step_args(params, geo, "", jax.tree.map(lambda _: one, params), lambda s: one, one)
        return gpt2_family.fused_programs("mosaic")[0], (p, pool, *rest), (1,), pool
    if family == "moe":
        from seldon_core_tpu.models import moe_decoder as md

        cfg = md.MoEDecoderConfig(vocab=98304, hidden=2304, layers=4, heads=32, kv_heads=4, head_dim=128, ffn=896,
                                  experts=64, experts_per_tok=8, window=1024, yarn_factor=16.0, yarn_original=8192)
        fam = md.moe_family(cfg)
        params = on_chip(jax.eval_shape(lambda: md.init_moe_decoder(cfg, 0, jnp.bfloat16)))
        pool = on_chip(jax.eval_shape(lambda: fam.paged_kv_init(params, 3520, 16, jnp.bfloat16)))
        args = (params, pool, *small(16, 204), arr((16,), jnp.bool_))
        return fam.fused_programs()[0], args, (1,), pool
    from seldon_core_tpu.models import hybrid_decoder as hd

    cfg = hd.HybridDecoderConfig(
        vocab=100352, hidden=2048, layers=3, attn_layers=(1,), heads=32, kv_heads=8, head_dim=64, ffn=8192,
        ssm_heads=64, ssm_head_dim=64, ssm_state=128, attention_multiplier=0.015625,
    )
    fam = hd.hybrid_family(cfg)
    params = on_chip(jax.eval_shape(lambda: hd.init_hybrid_decoder(cfg, 0, jnp.bfloat16)))
    pool = on_chip(jax.eval_shape(lambda: fam.paged_kv_init(params, 3400, 16, jnp.bfloat16)))
    rec = on_chip(jax.eval_shape(lambda: fam.state_init(params, 69)))
    args = (params, pool, rec, *small(64, 52), arr((64,), jnp.bool_))
    return fam.fused_programs()[0], args, (1, 2), (*pool, *rec)


def _mla_cell(one, layers: int):
    """(family, parameters, pool) as shapes on the described chip: the
    a.x-k1 cell's widths, experts held and vocabulary slice, the leading dense
    layer + ``layers - 1`` expert layers, its 8192 latent pages of 16 rows."""
    from seldon_core_tpu.models import mla_decoder as mla

    cfg = mla.MLADecoderConfig(
        vocab=20480, hidden=7168, layers=layers, heads=64, q_rank=1536, kv_rank=512, nope_dim=128, rope_dim=64,
        v_dim=128, dense_layers=1, dense_ffn=18432, ffn=2048, experts=192, experts_held=12, first_expert=0,
        experts_per_tok=8, n_group=8, topk_group=4, yarn_factor=32.0, yarn_original=4096,
    )
    fam = mla.mla_family(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), tree)

    params = on_chip(jax.eval_shape(lambda: mla.init_mla_decoder(cfg, 0, jnp.bfloat16)))
    pool = on_chip(jax.eval_shape(lambda: fam.paged_kv_init(params, 8192, 16, jnp.bfloat16)))
    return fam, params, pool


def _assert_chunk_reads_the_plane_through_the_kernel(text: str, n: int, c: int, heads: int, pages: int, layers: int):
    """A latent chunk program as a TPU builds it since PR 45: ONE Mosaic call
    a layer under ``attn/mla_core`` (``mla_chunk_attention``), the fold and
    ``Wuv`` under ``attn/mla_absorb``; nothing walks and nothing branches on
    the dispatch's live queries; no block of pages is gathered, no float32
    score tensor ``[rows, heads, queries, keys]`` of any key block and no
    expanded per-head ``kv`` block ``[rows, keys, heads, nope + v]`` exists."""
    kernels = [ln for ln in text.splitlines() if 'custom_call_target="tpu_custom_call"' in ln and "/attn/mla_core/" in ln]
    assert len(kernels) == layers and all("mla_chunk_attention" in ln for ln in kernels)
    assert re.search(r"/attn/mla_absorb/", text)
    assert "/mla_core/while" not in text and "/attn/cond/" not in text and "/mla_expand/" not in text
    from seldon_core_tpu.ops.mla import CHUNK_BLOCK_PAGES, block_pages

    bp = block_pages(n, heads, c, 16, pages)  # what the walk of this entry gathered at a time
    assert not re.findall(r"bf16\[%d,%d,16,640\]" % (n, bp), text)
    keys = "(?:%d|%d)" % (bp * 16, CHUNK_BLOCK_PAGES * 16)  # of the walk's block, or of the kernel's
    assert not re.findall(r"f32\[%d,%d,%d,%s\]" % (n, heads, c, keys), text)  # scores of a key block
    assert not re.findall(r"bf16\[%d,%s,%d,256\]" % (n, keys, heads), text)  # a block's heads expanded


@pytest.mark.parametrize("cell", ["a.x-k1", "xing4.0-29b-a4b"])
@pytest.mark.parametrize("entry", [(2, 16), (2, 64), (2, 256), (4, 256)], ids=lambda e: "%d_%d" % e)
def test_the_chunk_kernel_compiles_at_the_latent_cells_ladder_entries(topo, cell, entry):
    """``mla_chunk_attention`` alone at the four chunk entries of the two
    latent cells (64 heads over 532-entry tables of a 7-layer plane; 32 heads
    over 144-entry tables of a 20-layer one): Mosaic takes the query blocks
    (``CHUNK_Q_ROWS`` query-head rows, or the whole chunk where it is
    shorter), the run DMAs and the scratch within its VMEM."""
    from seldon_core_tpu.ops import mla as mla_ops

    one = SingleDeviceSharding(topo.devices[0])
    heads, pages, layers, n_pages = (64, 532, 7, 8192) if cell == "a.x-k1" else (32, 144, 20, 6144)
    n, m = entry

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def attend(qc, plane, layer, bt, n_keys, q_first, counts):
        runs = mla_ops.page_runs(bt, n_keys, 16)
        return mla_ops.mla_chunk_attention(qc, plane, layer, bt, n_keys, q_first, counts, runs, heads=heads, rank=512, scale=0.1)

    vec = arr((n,), jnp.int32)
    compiled = jax.jit(attend).lower(
        arr((n, m * heads, 640), jnp.bfloat16), arr((layers, n_pages, 16, 640), jnp.bfloat16), arr((), jnp.int32),
        arr((n, pages), jnp.int32), vec, vec, vec,
    ).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and "mla_chunk_attention" in text
    assert not re.findall(r"bf16\[%d,%d,16,640\]" % (layers, n_pages) + r"\S* copy\(", text)  # the plane stays where it is
    tq = mla_ops._query_block(m, heads)
    assert tq * heads == min(mla_ops.CHUNK_Q_ROWS, m * heads) and mla_ops.kernel_takes("mosaic", m, 512, heads)


def test_the_chunk_kernel_refuses_by_name_what_mosaic_cannot_tile():
    """Before Mosaic sees it: a query block of 4 rows (257 queries, a prime,
    of 4 heads) and a latent of 16 lanes raise ``kernel_tiles``' name, and
    ``kernel_takes`` keeps such a program on the walk."""
    from seldon_core_tpu.ops import mla as mla_ops

    z = jnp.zeros((2,), jnp.int32)
    plane = jnp.zeros((1, 9, 16, 128), jnp.bfloat16)
    for m, rank in ((257, 128), (128, 16)):
        assert not mla_ops.kernel_takes("mosaic", m, rank, 4)
        with pytest.raises(ValueError, match="mla_chunk_attention cannot tile .* keeps the walk"):
            mla_ops.mla_chunk_attention(jnp.zeros((2, m * 4, 128), jnp.bfloat16), plane, 0, jnp.zeros((2, 8), jnp.int32), z + 1, z,
                                        z + 3, jnp.zeros((2, 4), jnp.int32), heads=4, rank=rank, scale=1.0)


def test_the_latent_step_lowers_to_the_text_it_had_before_the_chunk_kernel(topo, monkeypatch):
    """PR 45 gave the chunks a kernel and moved what the two kernels share
    (a block's DMAs, a block of the online softmax) into functions of their
    own: the STEP program at the a.x-k1 cell's widths still lowers to the
    parent's text (193f7d8; the Mosaic body compared without its source
    locations), so ``step_device_ms``, ``mla_decode_roofline`` and
    ``step_roofline.mla`` read a program that did not change. Re-made at
    PR 49 (d9fb0f47... before), which gave ``moe_held_ffn``'s counts two more
    entries, constants 0 at the step's 64 rows (the masked form): the
    counters' vector is six wide where it was four and the readback two
    elements longer; text for text nothing else differs from the parent's
    but the numbering of the values after them."""
    import hashlib

    from seldon_core_tpu.ops import moe

    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    one = SingleDeviceSharding(topo.devices[0])
    fam, params, pool = _mla_cell(one, layers=2)
    i32, f32, n = jnp.int32, jnp.float32, 64

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    args = (arr((n, 532), i32), arr((n,), i32), arr((n,), i32), arr((n,), f32), arr((n,), i32), arr((), i32), arr((), i32),
            arr((n,), jnp.bool_))
    step, _chunk = fam.fused_programs("mosaic")
    text = _without_locations(jax.jit(step, donate_argnums=(1,)).lower(params, pool, *args).as_text())
    text = re.sub(r"loc\(.*?\)\n|#loc.*\n", "", text)
    assert text.count('"mosaic:') == 1 and 'kernel_name = "mla_decode_attention"' in text
    assert hashlib.sha256(text.encode()).hexdigest() == "b3c77aa02c3e3683a60ef5da091cca95d61f3a6b02d15e3c69bfafaf4adc17a9"


@pytest.mark.parametrize("program", ["step", "chunk_2_16", "chunk_2_64", "chunk_2_256", "chunk_4_256"])
def test_latent_family_programs_compile_in_place_at_a_x_k1_widths(topo, program, monkeypatch):
    """The fourth family's fused step (64 slots) and the chunk ladder's four
    entries at the a.x-k1 cell's widths, the dense layer + one expert layer,
    as the program set builds them on a TPU (``_step_attn_kernel`` answers
    "mosaic" for the cell's plane): the donated latent plane comes back
    aliased and no op copies it (a 576-wide row made the chip's compiler lay
    the plane out pages-minor and copy it twice a step:
    ``MLADecoderConfig.row_width``); nothing the size of every slot's
    gathered table exists in float32. The STEP reads the plane through
    ops/mla.py's step kernel: one Mosaic call a layer under
    ``attn/mla_core``, no gathered block and no ``while`` there. Every CHUNK
    reads it through the chunk's kernel (PR 45), absorbed whatever its
    length: one Mosaic call a layer, no walk, no ``absorb_short`` branch, no
    score tensor and no expanded block in HBM; the grouped expert products
    of the 256-token chunks are the Pallas kernel."""
    from seldon_core_tpu.ops import moe
    from seldon_core_tpu.serving.decode_programs import _step_attn_kernel

    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    one = SingleDeviceSharding(topo.devices[0])
    fam, params, pool = _mla_cell(one, layers=2)
    assert len(pool) == 1 and pool[0].shape == (2, 8192, 16, 640)
    i32, f32 = jnp.int32, jnp.float32

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    chosen = _step_attn_kernel(fam, pool, None, 64, 1)
    assert chosen == "mosaic"
    step, chunk = fam.fused_programs(chosen)
    if program == "step":
        n, c, fn = 64, 1, step
        args = (arr((n, 532), i32), arr((n,), i32), arr((n,), i32), arr((n,), f32), arr((n,), i32), arr((), i32),
                arr((), i32), arr((n,), jnp.bool_))
    else:
        n, c = (int(v) for v in program.split("_")[1:])
        fn = chunk
        args = (arr((n, 532), i32), arr((n, c), i32), arr((n,), i32), arr((n,), i32), arr((n,), f32),
                arr((n,), i32), arr((), i32), arr((), i32))
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(params, pool, *args).compile()
    text = compiled.as_text()
    plane_bytes = int(np.prod(pool[0].shape)) * 2
    assert compiled.memory_analysis().alias_size_in_bytes >= plane_bytes
    plane = re.escape("bf16[2,8192,16,640]")
    assert not [ln for ln in text.splitlines() if re.search(r"= " + plane + r"\S* copy\(", ln)]
    # a row's whole table is 532 pages = 8512 keys: nothing is gathered at that length, in any dtype
    # (the walk takes 8 to 64 pages at a time), and nothing 640 lanes wide is float32
    assert not re.findall(r"\[%d,(?:532,16|8512),[0-9,]*\]" % n, text)
    # the one plane's write, a layer: a row a slot in the step, whole pages in a chunk (PR 40)
    want = (64, "row") if program == "step" else (n * (c // 16 + 1), "page")
    assert _pool_scatters(text, pool) == [want] * 2
    where = "step" if program == "step" else "chunk"
    assert re.search(r'op_name="jit\(_fused_%s\)/attn/mla_core/' % where, text)
    assert re.search(r"/attn/mla_absorb/", text)
    kernels = [ln for ln in text.splitlines() if 'custom_call_target="tpu_custom_call"' in ln and "/attn/mla_core/" in ln]
    if program == "step":
        assert len(kernels) == 2 and all("mla_decode_attention" in ln for ln in kernels)  # a call a layer: Mosaic
        # no block of pages is gathered, whatever its length, and nothing walks
        assert not re.findall(r"bf16\[%d,\d+,16,640\]" % n, text) and "/mla_core/while" not in text
        assert text.count("tpu_custom_call") == 2  # 64 rows: the masked expert form, no grouped kernel
        return
    assert fam.chunk_attn(chosen, c) == "kernel"
    _assert_chunk_reads_the_plane_through_the_kernel(text, n, c, 64, 532, layers=2)
    assert (text.count("tpu_custom_call") >= 4) == (n * c > moe.MASKED_MAX_ROWS)  # + gate_up and down, grouped


@pytest.mark.parametrize(
    "program, sinkhorn",
    [("step", "kernel"), ("step", "plain"), ("chunk_4_256", "kernel"), ("chunk_4_256", "plain"),
     ("chunk_2_16", "kernel"), ("chunk_2_64", "kernel"), ("chunk_2_256", "kernel")],
)
def test_latent_family_with_four_streams_compiles_in_place_at_xing_widths(topo, program, sinkhorn, monkeypatch):
    """The fourth family with ``hc_mult`` 4 and the bias-selected gate at the
    xing4.0-29b-a4b cell's widths (hidden 3584 in four streams, 32 heads, a
    768-wide query rank, 8 of 64 experts of 1024 held, 6144 latent pages),
    the two dense layers + one expert layer: the step (its kernel at 32
    heads, a call a layer) and the chunk ladder's four entries (the chunk's
    kernel at 32 heads, a call a layer: PR 45). The donated plane
    comes back aliased and uncopied; the stream maps' three scopes are in the
    compiled text under ``qkv``, ``attn_out`` and ``mlp``, the Sinkhorn
    iterations ONE Mosaic call a block as a TPU builds them (``mhc_sinkhorn``)
    or, in the plain form, one ``while`` a block and not forty unrolled
    stages; nothing
    ``[rows, 4, 4, hidden]`` exists (the stream mix is written out over the
    stream axis) and the streams-major state is never copied whole into
    float32 (the maps' product reads the bfloat16 state as stored; every
    mix converts inside its own fusion)."""
    from seldon_core_tpu.models import mla_decoder as mla
    from seldon_core_tpu.ops import mhc, moe
    from seldon_core_tpu.serving.decode_programs import _step_attn_kernel

    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    monkeypatch.setattr(mhc, "_sinkhorn_mode", lambda: "mosaic" if sinkhorn == "kernel" else "")
    jax.clear_caches()  # the other form's trace of the same program is not this one's
    one = SingleDeviceSharding(topo.devices[0])
    cfg = mla.MLADecoderConfig(
        vocab=16384, hidden=3584, layers=3, heads=32, q_rank=768, kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128,
        dense_layers=2, dense_ffn=9216, ffn=1024, experts=64, experts_held=8, first_expert=0, experts_per_tok=4,
        n_group=0, topk_group=0, gate_bias=True, routed_scale=2.0, yarn_factor=64.0, yarn_original=4096, max_len=262144,
        hc_mult=4,
    )
    fam = mla.mla_family(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), tree)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = on_chip(jax.eval_shape(lambda: mla.init_mla_decoder(cfg, 0, jnp.bfloat16)))
    pool = on_chip(jax.eval_shape(lambda: fam.paged_kv_init(params, 6144, 16, jnp.bfloat16)))
    assert params["layers"][0]["hc_attn"]["phi"].shape == (4 * 3584, 24) and "router_bias" in params["layers"][2]["moe"]
    i32, f32 = jnp.int32, jnp.float32
    chosen = _step_attn_kernel(fam, pool, None, 32, 1)
    assert chosen == "mosaic"
    step, chunk = fam.fused_programs(chosen)
    if program == "step":
        n, c, fn, where = 64, 1, step, "step"
        args = (arr((n, 144), i32), arr((n,), i32), arr((n,), i32), arr((n,), f32), arr((n,), i32), arr((), i32),
                arr((), i32), arr((n,), jnp.bool_))
    else:
        n, c = (int(v) for v in program.split("_")[1:])
        fn, where = chunk, "chunk"
        args = (arr((n, 144), i32), arr((n, c), i32), arr((n,), i32), arr((n,), i32), arr((n,), f32),
                arr((n,), i32), arr((), i32), arr((), i32))
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(params, pool, *args).compile()
    text = compiled.as_text()
    assert compiled.memory_analysis().alias_size_in_bytes >= int(np.prod(pool[0].shape)) * 2
    plane = re.escape("bf16[3,6144,16,640]")
    assert not [ln for ln in text.splitlines() if re.search(r"= " + plane + r"\S* copy\(", ln)]
    for parent, name in (("qkv", "mhc_map"), ("qkv", "mhc_pre"), ("attn_out", "mhc_post"), ("mlp", "mhc_map"),
                         ("mlp", "mhc_pre"), ("mlp", "mhc_post")):
        assert re.search(r'op_name="jit\(_fused_%s\)/%s/%s/' % (where, parent, name), text), (parent, name)
    loops = {m for m in re.findall(r'op_name="jit\(_fused_\w+\)/(\w+)/mhc_map/while/body', text)}
    calls = [ln for ln in text.splitlines() if 'custom_call_target="tpu_custom_call"' in ln and "/mhc_map/" in ln]
    if sinkhorn == "kernel":
        assert len(calls) == 6 and not loops  # a call a block: two blocks a layer
    else:
        assert loops == {"qkv", "mlp"} and not calls  # the Sinkhorn loop of both blocks' maps
    assert not re.findall(r"\[4,4,(?:%d,%d|%d),3584\]|\[(?:%d,%d|%d),4,4,3584\]" % ((n, c, n * c) * 2), text)
    # the state is streams-major and the compiler keeps each stream a [rows, queries, 3584] array of its own:
    # never a float32 copy of all four, in either order of the axes
    assert not re.findall(r"f32\[4,%d,%d,3584\]|f32\[%d,%d,4,3584\]" % (n, c, n, c), text)
    kernels = [ln for ln in text.splitlines() if 'custom_call_target="tpu_custom_call"' in ln and "/attn/mla_core/" in ln]
    assert len(kernels) == 3  # a kernel call a layer, at 32 heads: the step's, or the chunk's
    if program == "step":
        assert all("mla_decode_attention" in ln for ln in kernels)
    else:
        assert fam.chunk_attn(chosen, c) == "kernel"
        _assert_chunk_reads_the_plane_through_the_kernel(text, n, c, 32, 144, layers=3)


@pytest.mark.parametrize("program", ["step", "step_kernel", "chunk_2_64"])
def test_conv_family_updates_pool_and_state_rows_in_place_at_lfm2_widths(topo, program):
    """The fifth family's fused step (64 slots; through the gather, and with
    the grouped-query kernel as on a TPU) and (2, 64) chunk at the
    lfm2-24b-a2b cell's widths, 8 of its 40 layers (two periods: 6 conv + 2
    attention layers, the 2 dense MLPs and 6 expert layers of 8 held of 64):
    the donated pool AND the donated conv state rows come back aliased, no op
    copies an array the size of a state array, and the family's scopes name
    the conv operator, the QK norm and the routed experts."""
    from seldon_core_tpu.models import conv_decoder as cd

    one = SingleDeviceSharding(topo.devices[0])
    cfg = cd.ConvDecoderConfig(
        vocab=65536, hidden=2048, layers=8, attn_layers=(2, 6), heads=32, kv_heads=8, head_dim=64, dense_layers=2,
        dense_ffn=11776, ffn=1536, experts=64, experts_held=8, first_expert=0, experts_per_tok=4,
    )
    fam = cd.conv_family(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), tree)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = on_chip(jax.eval_shape(lambda: cd.init_conv_decoder(cfg, 0, jnp.bfloat16)))
    n, rows_total = 64, 64 + 4 + 1
    pool = on_chip(jax.eval_shape(lambda: fam.paged_kv_init(params, 6144, 16, jnp.bfloat16)))
    rec = on_chip(jax.eval_shape(lambda: fam.state_init(params, rows_total)))
    assert pool[0].shape == (2, 6144, 16, 512) and len(rec) == 6 and rec[0].shape == (rows_total, 4096)
    step, chunk = fam.fused_programs("mosaic" if program == "step_kernel" else "")
    i32, f32 = jnp.int32, jnp.float32
    if program.startswith("step"):
        args = (arr((n, 144), i32), arr((n,), i32), arr((n,), i32), arr((n,), f32), arr((n,), i32), arr((), i32),
                arr((), i32), arr((n,), jnp.bool_))
        fn = step
    else:
        r, c = 2, 64
        args = (arr((r, 144), i32), arr((r, c), i32), arr((r,), i32), arr((r,), i32), arr((r,), f32),
                arr((r,), i32), arr((), i32), arr((), i32), arr((3, r), i32))
        fn = chunk
    compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(params, pool, rec, *args).compile()
    donated = sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in (*pool, *rec))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= donated
    text = compiled.as_text()
    state = re.escape("f32[%d,4096]" % rows_total)
    assert not [ln for ln in text.splitlines() if re.search(r"= " + state + r"\S* copy\(", ln)]
    where = "chunk" if program == "chunk_2_64" else "step"
    for scope in ("qkv/conv_in", "attn/conv_mix", "attn_out/conv_out", "qkv/qk_norm", "qkv/rope", "mlp/dense",
                  "mlp/moe_router", "mlp/moe_experts"):
        assert re.search(r'op_name="jit\(_fused_%s\)/%s/' % (where, scope), text), scope
    # the attention layers' K and V: a row a slot in the step, 2 x 5 whole pages in the (2, 64) chunk (PR 40)
    want = (2 * 5, "page") if program == "chunk_2_64" else (64, "row")
    assert _pool_scatters(text, pool) == [want] * 4
    if program == "step_kernel":
        _assert_step_reads_the_pool_through_the_kernel(text, 2)
        assert mem.temp_size_in_bytes < 64 * 144 * 16 * 512 * 4  # under ONE gathered float32 cache


def test_the_conv_step_with_the_kernel_lowers_to_the_text_it_had_before_the_windowed_form(topo):
    """The short-convolution family's step with ops/gqa_decode.py's kernel at
    the lfm2-24b-a2b cell's widths (32 / 8 heads of 64, no first key, no padded
    head row), 8 layers: what it lowers to for the chip is what it lowered to
    before the kernel took a windowed table and 6- and 9-head groups (PR 48's
    parent, e75f1e9, hashed there with ``_without_locations``): the windowed
    form is a static variant, and a call without ``first`` traces the kernel
    it traced, argument for argument. Re-made at PR 49 (32aa74fc... before)
    for the two constant counts ``moe_held_ffn`` gained, as the latent
    step's above."""
    import hashlib

    from seldon_core_tpu.models import conv_decoder as cd

    one = SingleDeviceSharding(topo.devices[0])
    cfg = cd.ConvDecoderConfig(
        vocab=65536, hidden=2048, layers=8, attn_layers=(2, 6), heads=32, kv_heads=8, head_dim=64, dense_layers=2,
        dense_ffn=11776, ffn=1536, experts=64, experts_held=8, first_expert=0, experts_per_tok=4,
    )
    fam = cd.conv_family(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), tree)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = on_chip(jax.eval_shape(lambda: cd.init_conv_decoder(cfg, 0, jnp.bfloat16)))
    pool = on_chip(jax.eval_shape(lambda: fam.paged_kv_init(params, 6144, 16, jnp.bfloat16)))
    rec = on_chip(jax.eval_shape(lambda: fam.state_init(params, 69)))
    n, i32, f32 = 64, jnp.int32, jnp.float32
    args = (arr((n, 144), i32), arr((n,), i32), arr((n,), i32), arr((n,), f32), arr((n,), i32), arr((), i32),
            arr((), i32), arr((n,), jnp.bool_))
    step, _chunk = fam.fused_programs("mosaic")
    text = _without_locations(jax.jit(step, donate_argnums=(1, 2)).lower(params, pool, rec, *args).as_text())
    assert text.count('"mosaic:') == 1  # the two attention layers' calls lower the kernel once
    assert hashlib.sha256(text.encode()).hexdigest() == "9ee0656864cdc6b1a498fb80158d35982ef8b97c3c39cf52720ca1c7220c21f1"


@pytest.mark.parametrize("family", ["gpt2", "moe", "hybrid"])
def test_step_programs_sampler_draws_and_selects_only_behind_its_gates(topo, family):
    """Each family's fused step at its cell's rows and vocabulary: what runs
    whatever the dispatch's ``temps`` say holds the argmax and the two
    predicates; the ``[rows, vocab]`` draw and the top_k cutoff's loop are
    in a ``conditional``'s branches, the compiler kept the ``conditional``,
    nothing sorts, and the gate costs no donation: the pool (and the state
    rows) still alias whole and the arguments are the arrays handed in."""
    step, args, donate, donated = _family_step(family, SingleDeviceSharding(topo.devices[0]))
    compiled = jax.jit(step, donate_argnums=donate).lower(*args).compile()
    text = compiled.as_text()
    sampler = [ln for ln in text.splitlines() if "/sample/" in ln]
    drawn = [ln for ln in sampler if "_gumbel" in ln]
    looped = [ln for ln in sampler if "/cond/branch_1_fun/cond/branch_1_fun/while" in ln]
    assert drawn and looped  # the pattern reads the text
    assert all("/sample/cond/branch_1_fun/" in ln for ln in drawn + looped)
    assert not [ln for ln in sampler if re.search(r"\ssort\(", ln)]  # the router's top-k may sort 64 experts
    ungated = _ungated_lines(text)
    assert any(" conditional(" in ln and '/sample/cond"' in ln for ln in ungated)  # the outer gate, in the open
    assert any("/sample/" in ln and "reduce" in ln for ln in ungated)  # the argmax
    behind = [ln for ln in ungated if "/sample/cond/" in ln and " conditional(" not in ln]
    assert not behind, behind[:3]
    mem = compiled.memory_analysis()
    nbytes = lambda tree: sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in jax.tree.leaves(tree))  # noqa: E731
    assert mem.alias_size_in_bytes >= nbytes(donated)
    # the arguments' bytes and their tile padding (the (n,) vectors round up), nothing else
    assert nbytes(args) <= mem.argument_size_in_bytes < nbytes(args) * 1.001 + 2**16


# the grouped-query chunk kernel (ops/gqa_decode.py gqa_chunk_attention) at the five cells' geometries:
# (rows, chunk, query heads, K/V heads, head_dim, table entries, window)
GQA_CHUNK_CELLS = {
    "laguna_full_4_256": (4, 256, 48, 8, 128, 464, 0),
    "laguna_window_4_256": (4, 256, 72, 8, 128, 49, 512),
    "laguna_window_2_16": (2, 16, 72, 8, 128, 34, 512),
    "mellum2_2_64": (2, 64, 32, 4, 128, 208, 0),
    "nemotron_4_256": (4, 256, 32, 2, 128, 144, 0),
    "lfm2_4_256": (4, 256, 32, 8, 64, 144, 0),
    "lfm2_2_16": (2, 16, 32, 8, 64, 144, 0),
    "granite_2_64": (2, 64, 32, 8, 64, 48, 0),
    "qwen3_next_4_256": (4, 256, 16, 2, 256, 144, 0),  # a head of two lane tiles: one tile of 256 (PR 57)
    "qwen3_next_2_16": (2, 16, 16, 2, 256, 144, 0),
}


@pytest.mark.parametrize("cell", sorted(GQA_CHUNK_CELLS))
def test_the_gqa_chunk_kernel_compiles_at_the_five_cells_geometries(topo, cell):
    """Mosaic takes the kernel alone for the described chip at each cell's
    head group, head width, table and chunk entry (heads of 64 two a lane
    tile; the sliding layers' windowed sub-table with the window as a static
    variant), as ``gqa_chunk_tiles`` says it will: one Mosaic call, and
    temporaries no larger than the re-laid queries and their output."""
    from seldon_core_tpu.ops import gqa_decode as gqa

    n, m, heads, kv_heads, d, pages, window = GQA_CHUNK_CELLS[cell]
    one = SingleDeviceSharding(topo.devices[0])

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    assert gqa.gqa_chunk_tiles("mosaic", m, heads, kv_heads, d)
    groups = np.prod(gqa._table_blocks(pages, gqa.CHUNK_BLOCK_PAGES)[1:])
    w, i32 = kv_heads * d, jnp.int32
    plane = arr((2, 4096, 16, w), jnp.bfloat16)
    args = (arr((n, m, heads, d), jnp.bfloat16), plane, plane, arr((), i32), arr((n, pages), i32), arr((n,), i32),
            arr((n,), i32), arr((n,), i32), arr((n + 1,), i32), arr((n, int(groups)), i32))
    fn = functools.partial(gqa.gqa_chunk_attention, scale=d**-0.5, window=window)
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and "gqa_chunk_attention" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 6 * n * m * heads * 128 * 2


@pytest.mark.parametrize("program", ["step", "step_kernel", "chunk_4_256", "chunk_4_256_kernel"])
def test_the_two_page_kinds_programs_compile_at_the_long_context_cells_widths(topo, monkeypatch, program):
    """The sparse-expert family, as PR 47 left it and (``step_kernel``) with
    the step PR 48 gave it on a TPU, at the laguna-s-2.1 cell's
    widths (48 / 72 query heads over 8 key/value heads of 128, the per-head
    gate, half-rotary full layers, a dense layer, a shared expert and 32 of 256
    routed ones) over its leading dense + full layer and two sliding layers:
    the step of 64 slots and the (4, 256) chunk over 464-entry tables of BOTH
    page kinds (14,000 full-kind pages, the window kind's derived count). Both
    kinds' planes alias whole, and no float32 copy of a whole context exists
    for a SLIDING layer: its gather is the window's pages. With the kernel
    the step has a Mosaic call a layer under its kind's ``attn`` scope (two
    lowerings: 48 heads over the 464-entry table, 72 padded to 80 over the
    34-entry sub-table with a first key a slot; blocks of 64 pages of rows of
    1024 are 8 MiB of VMEM scratch, which Mosaic takes), no gathered context
    of either kind, and temporaries of megabytes where the gather's are 2 GB.
    With it (``chunk_4_256_kernel``, PR 52) the chunk has one too: 48 heads
    over the 464-entry table and 72 over the 49-entry sub-table with the
    window as a static variant, the chunk's vectors made once a kind under
    its ``kv_gather`` scope, and no gathered context or score of either kind."""
    from seldon_core_tpu.models import moe_decoder as md
    from seldon_core_tpu.ops import moe
    from seldon_core_tpu.serving.kv_pool import window_pool_pages

    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    one = SingleDeviceSharding(topo.devices[0])
    cfg = md.MoEDecoderConfig(
        vocab=12544, hidden=3072, layers=3, heads=48, heads_window=72, kv_heads=8, head_dim=128, ffn=1024, experts=256,
        experts_per_tok=10, experts_held=32, window=512, period=4, full_first=True, rope_theta=500000.0,
        rope_theta_window=10000.0, rotary_full=0.5, yarn_factor=128.0, yarn_original=8192, max_len=1048576,
        attn_gate=True, dense_layers=1, dense_ffn=12288, shared_expert=True, routed_scale=2.5)
    fam = md.moe_family(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), tree)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = on_chip(jax.eval_shape(lambda: md.init_moe_decoder(cfg, 0, jnp.bfloat16)))
    n_win = window_pool_pages(64, 2, 512, 256, 16)
    assert n_win == 64 * 49 + 2 * 33 + 2
    pool = on_chip(jax.eval_shape(lambda: fam.paged_kv_init(params, (14000, n_win), 16, jnp.bfloat16)))
    assert [a.shape[:2] for a in pool] == [(1, 14000)] * 2 + [(2, n_win)] * 2
    i32, f32 = jnp.int32, jnp.float32
    step, chunk = fam.fused_programs("mosaic" if program.endswith("_kernel") else "")
    n = 4 if program.startswith("chunk_4_256") else 64
    bt = (arr((n, 464), i32), arr((n, 464), i32))
    tail = (arr((n,), f32), arr((n,), i32), arr((), i32), arr((), i32))
    if program.startswith("step"):
        fn, args = step, (params, pool, bt, arr((n,), i32), arr((n,), i32), *tail, arr((n,), jnp.bool_))
    else:
        fn, args = chunk, (params, pool, bt, arr((n, 256), i32), arr((n,), i32), arr((n,), i32), *tail)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    mem = compiled.memory_analysis()
    nbytes = lambda tree: sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in jax.tree.leaves(tree))  # noqa: E731
    assert mem.alias_size_in_bytes >= nbytes(pool)
    text = compiled.as_text()
    for scope in ("attn_out/gate", "mlp/shared_expert", "mlp/dense", "win/kv_gather", "full/attn"):
        assert f"/{scope}/" in text, scope
    # a sliding layer's gathered cache is its window's pages (34 or 50 of them), never the table's 464
    assert not re.findall(r"f32\[%d,8,7424,128\][^\n]*/win/" % n, text)
    assert mem.temp_size_in_bytes < 3 << 30
    if program.startswith("chunk_4_256"):
        # the two expert layers' grouped products (PR 49): a pair a layer over the 2,816 rows of ``held_capacity``,
        # none over all 10,240 assignments
        assert _grouped_product_rows(text) == [2816] * 4
    if program == "step_kernel":
        calls = re.findall(r'custom_call_target="tpu_custom_call"[^\n]*op_name="jit\(_fused_step\)/(full|win)/attn/', text)
        assert sorted(calls) == ["full", "win", "win"]  # a call a layer, under its kind's scope
        assert re.search(r'op_name="jit\(_fused_step\)/full/kv_gather/', text)  # the lengths and run flags
        assert not re.search(r"\[64,(464|34),16,1024\]|\[64,8,(7424|544),128\]", text)  # no gathered context, either kind
        assert mem.temp_size_in_bytes < 64 << 20
    if program == "chunk_4_256_kernel":
        assert fam.chunk_attn("mosaic", 256) == "kernel"
        calls = re.findall(r'custom_call_target="tpu_custom_call"[^\n]*op_name="jit\(_fused_chunk\)/(full|win)/attn/', text)
        assert sorted(calls) == ["full", "win", "win"]  # a call a layer, under its kind's scope
        assert re.search(r'op_name="jit\(_fused_chunk\)/(full|win)/kv_gather/', text)  # the chunk's vectors, once a kind
        # no gathered context of either kind (464 or 49 pages a row), and no float32 scores
        assert not re.search(r"\[4,(464|49),16,1024\]|\[4,8,(7424|784),128\]|f32\[\d+,\d+,\d+,(7424|784)\]", text)
        assert mem.temp_size_in_bytes < 1 << 30
