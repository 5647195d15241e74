"""The main path's Pallas kernels, compiled for a described v5e chip.

Interpret mode (tests/test_pallas_kernels.py) cannot show what the
chip's compiler refuses — a block that is not a legal TPU tile, a
matmul accumulator that is not 32-bit — so each kernel is also compiled
here at the shapes the repo advertises, for a ``v5e:2x2`` topology that
is described, not attached. Nothing runs; a compile that passes says
nothing about results or times.

All of it lives in this ONE file and behind fixtures: only one process
may load the TPU's library, so the topology is described inside a
fixture of the worker that got this file, never at import.
"""

import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip (the next one warns
    and compiles again): turn the cache off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _flash_ce(B, D):
    from predictionio_tpu.ops.pallas import flash_ce

    def fwd_bwd(u, v, u_idx, i_idx, w):
        ce = flash_ce.make_flash_ce(u_idx, i_idx, w, 0.07, jnp.bfloat16, B)
        return jax.value_and_grad(ce, argnums=(0, 1))(u, v)

    f32, i32 = jnp.float32, jnp.int32
    # forward and ONE backward where dv stays in VMEM, two past that
    n_kernels = 2 if flash_ce.backward_form(B, D) == "one_pass" else 3
    return fwd_bwd, [((B, D), f32), ((B, D), f32), ((B,), i32), ((B,), i32),
                     ((B,), f32)], n_kernels


def _topk_dot(n_items, D, B, k=16, n_excl=8):
    from predictionio_tpu.ops.pallas import topk_dot

    fn = topk_dot.make_topk_dot(n_items, D, B, k, n_excl)
    # the table as the index keeps it: [D, Ip], items on the lanes
    return fn, [((B, D), jnp.float32),
                (topk_dot.table_shape(D, n_items), jnp.float32),
                ((B, n_excl), jnp.int32)], 1


def _embed_update(N, B, E):
    from predictionio_tpu.ops.pallas import embed_update

    fn = functools.partial(embed_update.pallas_rowwise_adagrad, lr=0.01)
    f32 = jnp.float32
    return fn, [((N, E), f32), ((N,), f32), ((B,), jnp.int32),
                ((B, E), f32)], 1


def _expert_stream(T, dim, expert_dim, n):
    from predictionio_tpu.ops.pallas import expert_stream

    bf16 = jnp.bfloat16
    return expert_stream.expert_stream, [
        ((T, dim), jnp.float32), ((T, n), jnp.float32),
        ((n, dim, expert_dim), bf16), ((n, dim, expert_dim), bf16),
        ((n, expert_dim, dim), bf16)], 1


def _expert_groups(T, dim, expert_dim, n, top_k):
    from predictionio_tpu.ops.pallas import expert_stream

    bf16, i32 = jnp.bfloat16, jnp.int32
    return expert_stream.expert_groups, [
        ((T, dim), jnp.float32), ((T * top_k,), i32),
        ((T * top_k,), jnp.float32), ((n,), i32), ((n,), i32),
        ((n, dim, expert_dim), bf16), ((n, dim, expert_dim), bf16),
        ((n, expert_dim, dim), bf16)], 1


def _chunk_attend(C, H, d_qk, d_v, masked, slots=2, positions=33_792):
    """One mixer's walk at a configuration's widths: a chunk of ``C`` rows,
    ``H`` heads, keys ``d_qk`` wide (64 of them the shared RoPE key) beside
    values ``d_v``, latents of rank 512 cached 640 wide, with or without
    the rows' own sets."""
    from predictionio_tpu.ops.pallas import chunk_attend

    d_rope, kv_rank = 64, 512
    rope_at, wide = chunk_attend.key_layout(d_qk - d_rope, d_rope)
    fn = functools.partial(
        chunk_attend.chunk_attend, block=512, kv_rank=kv_rank, d_rope=d_rope,
        rope_at=rope_at, scale=d_qk ** -0.5)
    bf16, i32 = jnp.bfloat16, jnp.int32
    shapes = [((H, C, d_qk), bf16), ((H, kv_rank, wide), bf16),
              ((H, kv_rank, d_v), bf16), ((slots, positions, 640), bf16),
              ((), i32), ((), i32), ((), i32)]
    if masked:
        return (lambda *a: fn(*a[:-1], keep=a[-1])), shapes + [
            ((C, positions), jnp.int8)], 1
    return fn, shapes, 1


def _span_walk(B, S, kv_heads, group, d_k, d_v, diff, slots, positions):
    """An extension batch's walk at a configuration's widths: ``B`` rows of
    ``S`` positions, ``group`` query heads a key/value head, keys ``d_k``
    beside values ``d_v``, pairs of heads under ``diff``."""
    from predictionio_tpu.ops import gqa
    from predictionio_tpu.ops.pallas import span_walk

    dims = gqa.GQADims(dim=64, heads=kv_heads * group, kv_heads=kv_heads,
                       head_dim=d_k, v_head_dim=d_v, block_len=1, diff=diff)
    groups = gqa.walk_groups(dims)
    n = kv_heads // len(groups)
    fn = functools.partial(span_walk.span_walk, groups=groups, block=512,
                           scale=d_k ** -0.5)
    bf16, i32 = jnp.bfloat16, jnp.int32
    M = n * S * group
    return fn, [((B, len(groups), M, n * d_k), bf16), ((B, M, 1), i32),
                ((slots, positions, dims.cache_width), bf16), ((B,), i32),
                ((B,), i32)], 1


def _selective_scan(T, d_inner, d_state):
    """One Mamba-1 layer's scan over a chunk: ``x`` and ``delta`` rows,
    ``A``, ``B``, ``C`` and the incoming state, float32."""
    from predictionio_tpu.ops.pallas import selective_scan

    f32 = jnp.float32
    return selective_scan.selective_scan, [
        ((T, d_inner), f32), ((T, d_inner), f32), ((d_state, d_inner), f32),
        ((T, d_state), f32), ((T, d_state), f32),
        ((d_state, d_inner), f32)], 1


@pytest.mark.parametrize("build,args", [
    (_flash_ce, (8192, 128)),
    (_flash_ce, (4096, 64)),
    # a dv of 64 MiB that the one-pass backward keeps resident, and a
    # batch past the budget: the two-pass split
    (_flash_ce, (65_536, 256)),
    (_flash_ce, (131_072, 256)),
    (_topk_dot, (26_744, 64, 1)),
    (_topk_dot, (26_744, 64, 32)),
    (_topk_dot, (1_000_000, 128, 1)),
    (_embed_update, (1_000_000, 8192, 128)),
    # the sequence model's head: 16,384 item rows of width 6144, the tile
    # rule at its floor of one 128-lane row of items (a 3 MB tile)
    (_topk_dot, (16_384, 6144, 1, 16, 1)),
    (_topk_dot, (16_384, 6144, 8, 16, 1)),
    # the batched serve path's buckets at the ALS cell's catalogue
    (_topk_dot, (9_400_000, 64, 16, 16, 1)),
    (_topk_dot, (9_400_000, 64, 32, 16, 1)),
    (_topk_dot, (9_400_000, 64, 64, 16, 1)),
    # a small forward's expert layer: SDAR's block forward (8 rows x a block
    # of 4, 128 experts of 768: a whole expert a step, 18.9 MB of VMEM) and
    # LongCat's extension batch (8 x 8, 16 experts of 2048: chunks of 512
    # columns, 37.7 MB), each within the VMEM limit it asks for
    (_expert_stream, (32, 2048, 768, 128)),
    (_expert_stream, (64, 6144, 2048, 16)),
    # a prefill chunk's expert layer, 512 tokens' sorted pairs: SDAR's (128
    # experts of 768, top-8), granite's (36 held of 768 at hidden 4096,
    # top-10) and LongCat's (16 held of 2048 at hidden 6144, top-12, four
    # column chunks); x and the sum once each in VMEM beside two buffers of
    # an expert's matrices
    (_expert_groups, (512, 2048, 768, 128, 8)),
    (_expert_groups, (512, 4096, 768, 36, 10)),
    (_expert_groups, (512, 6144, 2048, 16, 12)),
    # A.X-K1's (12 held of 2048 at hidden 7168, top-8: the widest expert the
    # kernels are asked for, eight column chunks of 256), an extension
    # batch's 4 x 4 tokens and a chunk's 512; its head over 20,480 rows
    (_expert_stream, (16, 7168, 2048, 12)),
    (_expert_groups, (512, 7168, 2048, 12, 8)),
    (_topk_dot, (20_480, 7168, 1, 16, 1)),
    (_topk_dot, (20_480, 7168, 4, 16, 1)),
    # a prefill chunk's latent attention, the walk in one kernel: GLM-5's
    # widths (256-wide keys and values, under each row's own set and
    # without) and A.X-K1's and LongCat's (192 beside 128)
    (_chunk_attend, (512, 64, 256, 256, True)),
    (_chunk_attend, (512, 64, 256, 256, False)),
    (_chunk_attend, (512, 64, 192, 128, False)),
    # a chunk's selective scan at Phi-4-mini-flash's widths: five groups of
    # 1,024 channels, sixteen states a channel in registers
    (_selective_scan, (512, 5120, 16)),
    # an extension batch's walk over a span of keys and values, each row its
    # own blocks: Phi-4-mini-flash's (ten differential pairs of 64-wide
    # heads, an extension's 4 positions and a cross mixer's one), MiMo-V2.5's
    # full layers (keys of 192 beside values of 128, groups of 16: a key
    # head's lanes start inside a 128-lane row) and granite's one
    (_span_walk, (8, 4, 20, 2, 64, 64, True, 17, 33_792)),
    (_span_walk, (8, 1, 20, 2, 64, 64, True, 17, 33_792)),
    (_span_walk, (8, 4, 4, 16, 192, 128, False, 25, 25_600)),
    (_span_walk, (16, 4, 8, 4, 128, 128, False, 33, 8_704)),
], ids=["flash_ce-8192x128", "flash_ce-4096x64", "flash_ce-65536x256",
        "flash_ce-131072x256", "topk_dot-26744x64-B1",
        "topk_dot-26744x64-B32", "topk_dot-1Mx128-B1",
        "embed_update-1M-8192x128", "topk_dot-16384x6144-B1",
        "topk_dot-16384x6144-B8", "topk_dot-9400000x64-B16",
        "topk_dot-9400000x64-B32", "topk_dot-9400000x64-B64",
        "expert_stream-32x2048-128x768", "expert_stream-64x6144-16x2048",
        "expert_groups-512x2048-128x768", "expert_groups-512x4096-36x768",
        "expert_groups-512x6144-16x2048", "expert_stream-16x7168-12x2048",
        "expert_groups-512x7168-12x2048", "topk_dot-20480x7168-B1",
        "topk_dot-20480x7168-B4", "chunk_attend-512x64x256x256-keep",
        "chunk_attend-512x64x256x256", "chunk_attend-512x64x192x128",
        "selective_scan-512x5120x16", "span_walk-phi-extend",
        "span_walk-phi-cross", "span_walk-mimo", "span_walk-granite"])
def test_kernel_compiles_for_v5e(one_chip, no_compile_cache, build, args):
    fn, shapes, n_kernels = build(*args)
    text = _compiled_text(fn, shapes, one_chip)
    assert text.count("tpu_custom_call") >= n_kernels


def _compiled_text(fn, shapes, one_chip):
    specs = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
             for shape, dtype in shapes]
    return jax.jit(fn).lower(*specs).compile().as_text()


def _kernel_instructions(text):
    """Names of the compiled text's Pallas kernel instructions."""
    return re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target="
                      r"\"tpu_custom_call\"", text)


@pytest.mark.parametrize("build,args,names", [
    (_topk_dot, (26_744, 64, 1), ["topk_dot"]),
    # the stretch cell's shape: exactly two kernels a step
    (_flash_ce, (8192, 128), ["flash_ce_fwd", "flash_ce_bwd"]),
    (_flash_ce, (131_072, 256),
     ["flash_ce_fwd", "flash_ce_bwd_du", "flash_ce_bwd_dv"]),
    (_expert_stream, (32, 2048, 768, 128), ["expert_stream"]),
    (_expert_groups, (512, 2048, 768, 128, 8), ["expert_groups"]),
    (_chunk_attend, (512, 64, 256, 256, True), ["chunk_attend"]),
    (_selective_scan, (512, 5120, 16), ["selective_scan"]),
    (_span_walk, (8, 4, 20, 2, 64, 64, True, 17, 33_792), ["span_walk"]),
], ids=["topk_dot", "flash_ce", "flash_ce_two_pass", "expert_stream",
        "expert_groups", "chunk_attend", "selective_scan", "span_walk"])
def test_a_kernels_instruction_carries_its_name(one_chip, no_compile_cache,
                                                build, args, names):
    """A device trace's events are named by the instruction's text: the
    readers find a kernel by ``pallas_call(name=...)``, which must reach the
    compiled instruction (unnamed, it took an accidental one:
    ``tpu_custom_call.1``, ``jvp__.6``)."""
    fn, shapes, _ = build(*args)
    kernels = _kernel_instructions(_compiled_text(fn, shapes, one_chip))
    assert len(kernels) == len(names)
    # XLA appends .N; under autodiff with no scope around the call JAX
    # wraps the name (jvp_flash_ce_fwd_.1), inside the trainer's
    # twotower.flash_ce scope it does not (flash_ce_fwd.6)
    for name in names:
        assert sum(name in k for k in kernels) == 1, (name, kernels)


def test_a_lone_search_at_the_cells_shape_reads_the_table_as_it_is_stored(
        one_chip, no_compile_cache):
    """``als-amazon14``'s lone query (9,400,000 x 64, B = 1, k bucket 16,
    one exclusion column): ONE kernel, and nothing in front of it that
    re-tiles, transposes or pads the table: no ``copy``/``transpose``/
    ``pad`` instruction (fused or not) with a table-sized result."""
    n_items, D = 9_400_000, 64
    fn, shapes, _ = _topk_dot(n_items, D, 1, k=16, n_excl=1)
    text = _compiled_text(fn, shapes, one_chip)
    kernels = _kernel_instructions(text)
    assert len(kernels) == 1 and "topk_dot" in kernels[0], kernels
    table_sized = [
        m.group(0) for m in re.finditer(
            r"= \w+\[([\d,]*)\]\S* (copy|transpose|pad)\(", text)
        if math.prod(int(d) for d in m.group(1).split(",") if d)
        >= n_items * D]
    assert not table_sized, table_sized


def test_a_scope_reaches_xlas_own_fusions_through_the_scope_map(
        one_chip, no_compile_cache):
    """A scatter under ``named_scope`` stays ``%fusion.N`` on the chip; the
    scope is in the compiled text's metadata alone, which is what
    ``jaxmon.scope_map_of`` reads."""
    from predictionio_tpu.obs import jaxmon
    from predictionio_tpu.ops.twotower import _rowwise_adagrad

    def step(table, acc, idx, grad):
        with jax.named_scope("twotower.step"):
            with jax.named_scope("twotower.adagrad_user"):
                return _rowwise_adagrad(table, acc, idx, grad, 0.01)

    f32 = jnp.float32
    specs = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
             for shape, dtype in [((100_000, 128), f32), ((100_000,), f32),
                                  ((8192,), jnp.int32), ((8192, 128), f32)]]
    text = jax.jit(step).lower(*specs).compile().as_text()
    scopes = jaxmon.scope_map_of(text)
    fusions = {k: v for k, v in scopes.items() if k.startswith("fusion")}
    assert fusions and set(fusions.values()) == {"twotower.adagrad_user"}


@pytest.mark.parametrize("T,dim,expert_dim,n,chunk", [
    (32, 2048, 768, 128, 768), (64, 6144, 2048, 16, 512),
    (32, 4096, 2048, 16, 512)],
    ids=["sdar-block", "longcat-extend", "mimo-extend"])
def test_a_small_forwards_expert_layer_is_one_kernel_under_its_scope(
        one_chip, no_compile_cache, monkeypatch, T, dim, expert_dim, n,
        chunk):
    """``ops/moe.moe`` at the two cells' small shapes: one ``expert_stream``
    kernel, found under ``<scope>.experts`` through the scope map (a device
    trace's readers count it to the expert layer), with no sort beside it
    and its weights read as they are stored (no table-sized copy)."""
    from predictionio_tpu.obs import jaxmon
    from predictionio_tpu.ops import moe as moe_ops
    from predictionio_tpu.ops.pallas import expert_stream

    # the layer asks the backend, which is the CPU here: steer it to the
    # compiled kernel, as it chooses for itself on a TPU
    monkeypatch.setenv("PIO_PALLAS_INTERPRET", "0")
    dims = moe_ops.MoEDims(dim=dim, expert_dim=expert_dim, n_routed=n,
                           n_zero=0, top_k=8, scale=1.0, held=(0, n),
                           norm_topk=True)
    assert expert_stream.chunk_of(dim, expert_dim, 2) == chunk
    bf16 = jnp.bfloat16

    def struct(*shape, dtype=bf16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    p = {"w_r": struct(dim, n), "bias": struct(n, dtype=jnp.float32),
         "w_g": struct(n, dim, expert_dim), "w_u": struct(n, dim, expert_dim),
         "w_d": struct(n, expert_dim, dim)}

    def layer(p, x, valid):
        return moe_ops.moe(p, dims, x, valid, scope="seq.layer0.moe")

    text = jax.jit(layer).lower(
        p, struct(T, dim, dtype=jnp.float32),
        struct(T, dtype=jnp.bool_)).compile().as_text()
    kernels = _kernel_instructions(text)
    assert len(kernels) == 1 and "expert_stream" in kernels[0], kernels
    scopes = jaxmon.scope_map_of(text)
    assert scopes[kernels[0]] == "seq.layer0.moe.experts"
    # the router's top-k may sort; nothing of the expert part does
    assert not [i for i, scope in scopes.items()
                if "sort" in i and scope.endswith(".experts")]
    assert not re.search(
        rf"= bf16\[{n},\d+,\d+\]\S* (copy|transpose)\(", text)


@pytest.mark.parametrize("dim,expert_dim,n_routed,n_zero,held,top_k,chunk", [
    (2048, 768, 128, 0, 128, 8, 768), (4096, 768, 72, 0, 36, 10, 768),
    (6144, 2048, 512, 256, 16, 12, 512),
    (4096, 2048, 256, 0, 16, 8, 512)],
    ids=["sdar-chunk", "granite-chunk", "longcat-chunk", "mimo-chunk"])
def test_a_chunks_expert_layer_is_one_grouped_kernel_under_its_scope(
        one_chip, no_compile_cache, monkeypatch, dim, expert_dim, n_routed,
        n_zero, held, top_k, chunk):
    """``ops/moe.moe`` at a prefill chunk's 512 tokens and the three
    configurations' widths: ONE ``expert_groups`` kernel, found under
    ``<scope>.experts`` through the scope map, and no ``while`` left of the
    tile loop in the program (each touched expert's matrices cross HBM once:
    the kernel's grid walks them, the rows' loop is inside it); the weights
    read as they are stored; the VMEM limit the kernel asks for, which the
    compile stays under, is under 100 MiB (40.9 / 71.8 / 83.4 MB: two
    buffers of an expert's column chunk, x and the sum once each, a
    product's rows); the layer's temporaries outside it are small (0, 0 and
    0.7 MB as read here: the sorted lists)."""
    from predictionio_tpu.obs import jaxmon
    from predictionio_tpu.ops import moe as moe_ops
    from predictionio_tpu.ops.pallas import expert_stream

    monkeypatch.setenv("PIO_PALLAS_INTERPRET", "0")
    dims = moe_ops.MoEDims(dim=dim, expert_dim=expert_dim, n_routed=n_routed,
                           n_zero=n_zero, top_k=top_k, scale=1.0,
                           held=(0, held), norm_topk=True)
    assert expert_stream.chunk_of(dim, expert_dim, 2) == chunk
    T, n, bf16 = 512, held, jnp.bfloat16
    assert not moe_ops.small_forward(T)

    def struct(*shape, dtype=bf16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    p = {"w_r": struct(dim, dims.n_router),
         "bias": struct(dims.n_router, dtype=jnp.float32),
         "w_g": struct(n, dim, expert_dim), "w_u": struct(n, dim, expert_dim),
         "w_d": struct(n, expert_dim, dim)}

    def layer(p, x, valid):
        return moe_ops.moe(p, dims, x, valid, scope="seq.layer0.moe")

    compiled = jax.jit(layer).lower(
        p, struct(T, dim, dtype=jnp.float32),
        struct(T, dtype=jnp.bool_)).compile()
    text = compiled.as_text()
    kernels = _kernel_instructions(text)
    assert len(kernels) == 1 and "expert_groups" in kernels[0], kernels
    scopes = jaxmon.scope_map_of(text)
    assert scopes[kernels[0]] == "seq.layer0.moe.experts"
    assert not re.search(r"\bwhile\(", text)
    assert not re.search(
        rf"= bf16\[{n},\d+,\d+\]\S* (copy|transpose)\(", text)
    line = next(ln for ln in text.splitlines()
                if f"%{kernels[0]} = " in ln)
    limit = int(re.search(r'"memory_space":"1","offset":"0","size":"(\d+)"',
                          line).group(1))
    assert 32 << 20 < limit < 100 << 20, limit
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


@pytest.mark.parametrize("T,kernel", [(16, "expert_stream"),
                                      (512, "expert_groups")],
                         ids=["axk-extend", "axk-chunk"])
def test_an_expert_layer_that_picks_groups_first_is_one_kernel_under_its_scope(
        one_chip, no_compile_cache, monkeypatch, T, kernel):
    """``ops/moe.moe`` at A.X-K1's widths (hidden 7168, 12 of 192 experts of
    2048 held, a sigmoid router that keeps 4 of 8 groups before its top-8, a
    shared expert): the choice of groups adds no kernel and no loop, the one
    expert kernel lies under ``<scope>.experts`` in eight column chunks of
    256 and asks for under 100 MiB of VMEM, the shared expert's products
    under ``<scope>.shared``."""
    from predictionio_tpu.obs import jaxmon
    from predictionio_tpu.ops import moe as moe_ops
    from predictionio_tpu.ops.pallas import expert_stream

    monkeypatch.setenv("PIO_PALLAS_INTERPRET", "0")
    dim, expert_dim, n = 7168, 2048, 12
    dims = moe_ops.MoEDims(
        dim=dim, expert_dim=expert_dim, n_routed=192, n_zero=0, top_k=8,
        scale=2.5, held=(0, n), norm_topk=True, shared_dim=expert_dim,
        scoring="sigmoid", n_group=8, topk_group=4)
    assert expert_stream.chunk_of(dim, expert_dim, 2) == 256
    assert moe_ops.small_forward(T) == (kernel == "expert_stream")
    bf16 = jnp.bfloat16

    def struct(*shape, dtype=bf16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    p = {"w_r": struct(dim, 192), "bias": struct(192, dtype=jnp.float32),
         "w_g": struct(n, dim, expert_dim), "w_u": struct(n, dim, expert_dim),
         "w_d": struct(n, expert_dim, dim),
         "shared": {"w_g": struct(dim, expert_dim),
                    "w_u": struct(dim, expert_dim),
                    "w_d": struct(expert_dim, dim)}}

    def layer(p, x, valid):
        return moe_ops.moe(p, dims, x, valid, scope="seq.layer1.moe")

    text = jax.jit(layer).lower(
        p, struct(T, dim, dtype=jnp.float32),
        struct(T, dtype=jnp.bool_)).compile().as_text()
    kernels = _kernel_instructions(text)
    assert len(kernels) == 1 and kernel in kernels[0], kernels
    scopes = jaxmon.scope_map_of(text)
    assert scopes[kernels[0]] == "seq.layer1.moe.experts"
    assert "seq.layer1.moe.shared" in set(scopes.values())
    assert not re.search(r"\bwhile\(", text)
    assert not re.search(
        rf"= bf16\[{n},\d+,\d+\]\S* (copy|transpose)\(", text)
    line = next(ln for ln in text.splitlines()
                if f"%{kernels[0]} = " in ln)
    # the kernel's own scope (beside the shared expert's products XLA puts
    # it at an offset; the two together stay under the chip's 128 MiB)
    limit = int(re.search(r'"memory_space":"1","offset":"\d+","size":"(\d+)"',
                          line).group(1))
    assert 16 << 20 < limit < 100 << 20, limit


GLM_MIXER = dict(dim=6144, heads=64, d_nope=192, d_rope=64, d_v=256,
                 q_rank=2048, kv_rank=512, scale_q=False, scale_kv=False)
MIXERS = {
    "glm-5": dict(GLM_MIXER, index_heads=32, index_dim=128, index_topk=2048),
    "longcat": dict(dim=6144, heads=64, d_nope=128, d_rope=64, d_v=128,
                    q_rank=1536, kv_rank=512)}


@pytest.mark.parametrize("name,positions", [("glm-5", 33_280),
                                            ("longcat", 8_704)])
def test_a_chunks_latent_attention_is_one_kernel_under_the_mixers_scope(
        one_chip, no_compile_cache, monkeypatch, name, positions):
    """``ops/mla.prefill_chunk`` at GLM-5's widths (an index beside the
    latents: the walk under each row's set in one branch, over all in reach
    in the other) and at LongCat's: ONE ``chunk_attend`` instruction a walk,
    found under the mixer's scope through the scope map (``.attend`` under
    an index: where a trace's readers found the loop's fusions), no ``while``
    left of the walk over blocks in the attention's scope, no float32 scores
    ``[64, 512, 512]`` anywhere in the program; the donated latent cache is
    left in the layout it arrives in and none of it is copied; the kernel's
    VMEM limit is well under the chip's 128 MiB."""
    from predictionio_tpu.obs import jaxmon
    from predictionio_tpu.ops import mla

    monkeypatch.setenv("PIO_PALLAS_INTERPRET", "0")
    d = mla.MLADims(**MIXERS[name])

    def placed(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    p = placed(jax.eval_shape(
        lambda: mla.init(jax.random.PRNGKey(0), d, jnp.bfloat16)))
    cache = placed(jax.eval_shape(
        lambda: mla.init_cache(d, 5, positions, jnp.bfloat16)))
    scope = "seq.layer0.mla_a"

    def chunk(p, x, offset, cache):
        with jax.named_scope(scope):
            return mla.prefill_chunk(p, d, x, offset, cache, 1, 512, scope)

    compiled = jax.jit(chunk, donate_argnums=3).lower(
        p, jax.ShapeDtypeStruct((512, d.dim), jnp.float32,
                                sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        cache).compile()
    text = compiled.as_text()
    kernels = _kernel_instructions(text)
    scopes = jaxmon.scope_map_of(text)
    attend = scope + (".attend" if d.has_index else "")
    assert len(kernels) == (2 if d.has_index else 1), kernels
    assert all("chunk_attend" in k and scopes[k] == attend
               for k in kernels), [(k, scopes[k]) for k in kernels]
    walks = [i for i, s in scopes.items()
             if s == attend and i.startswith("while")]
    assert not walks, walks
    assert "f32[64,512,512]" not in text and "f32[1,64,512,512]" not in text
    held = rf"bf16\[5,{positions},640\]"
    # (a kernel's operand constraints name the logical order alone)
    layouts = set(re.findall(held + r"(\{[^}]*\})", re.sub(
        r"operand_layout_constraints=\{[^=]*\}, ", "", text)))
    assert layouts == {"{2,1,0:T(8,128)(2,1)}"}, layouts
    assert not re.search(rf"= {held}\S* copy\(", text)
    for k in kernels:
        line = next(ln for ln in text.splitlines() if f"%{k} = " in ln)
        limit = int(re.search(
            r'"memory_space":"1","offset":"\d+","size":"(\d+)"',
            line).group(1))
        assert 8 << 20 < limit < 64 << 20, limit


def test_the_kv_cache_is_left_as_it_is_handed_over(one_chip,
                                                   no_compile_cache):
    """Grouped-query attention's block step at the benchmark's widths (32
    query heads on 4 key/value heads of 128, 33 slots of 4,608 positions,
    1,024 values a position): the compiler keeps the donated cache in the
    layout it arrives in, positions on the sublanes, and copies none of it
    (a cache of 576 values a position was copied in and out of every call:
    ``ops/mla.cache_width``)."""
    from predictionio_tpu.ops import gqa

    dims = gqa.GQADims(dim=2048, heads=32, kv_heads=4, head_dim=128)
    bf16 = jnp.bfloat16

    def struct(*shape, dtype=bf16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    p = {"w_q": struct(2048, 4096), "w_k": struct(2048, 512),
         "w_v": struct(2048, 512), "w_o": struct(4096, 2048),
         "q_norm": struct(128), "k_norm": struct(128)}

    def step(p, x, pos, cache, slots, n_blocks):
        return gqa.block_step(p, dims, x, pos, cache, slots, n_blocks, 512)

    compiled = jax.jit(step, donate_argnums=3).lower(
        p, struct(8, 4, 2048, dtype=jnp.float32),
        struct(8, 4, dtype=jnp.int32), struct(33, 4608, dims.cache_width),
        struct(8, dtype=jnp.int32), struct(dtype=jnp.int32)).compile()
    text = compiled.as_text()
    layouts = set(re.findall(r"bf16\[33,4608,1024\](\{[^}]*\})", text))
    assert layouts == {"{2,1,0:T(8,128)(2,1)}"}, layouts
    assert not re.search(r"= bf16\[33,4608,1024\]\S* copy\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_an_extensions_rows_walk_the_span_where_it_lies(
        one_chip, no_compile_cache, monkeypatch):
    """The span's two kinds of reader at Phi-4-mini-flash's widths (40 query
    heads on 20 key/value heads of 64, differential pairs, biases; 17 slots
    of 33,792 positions of 2,560 values): layer 17's ``extend`` (a batch of
    8 x 4: the rows written, then walked) and a cross mixer's ``cross_rows``
    (8 x 1), in one program as the extension program holds them. ONE
    ``span_walk`` instruction a reader, under the mixer's ``.attend`` scope;
    no loop left there, no block of a row sliced out of the span (the
    parent's eight ``dynamic-slice`` fusions of ``bf16[1,1,512,2560]``); the
    donated span keeps the layout it arrives in and none of it is copied."""
    from predictionio_tpu.obs import jaxmon
    from predictionio_tpu.ops import gqa

    monkeypatch.setenv("PIO_PALLAS_INTERPRET", "0")
    same = dict(dim=2560, heads=40, kv_heads=20, head_dim=64, block_len=1,
                eps=1e-5, rope=False, qk_norm=False, bias=True, diff=True)
    full, cross = gqa.GQADims(**same), gqa.GQADims(cross=True, **same)
    assert full.cache_width == 2560

    def placed(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    p = placed(jax.eval_shape(lambda: (
        gqa.init(jax.random.PRNGKey(0), full, jnp.bfloat16),
        gqa.init(jax.random.PRNGKey(1), cross, jnp.bfloat16))))
    i32 = jnp.int32

    def step(p, x, pos, span, slots, own):
        out, span = gqa.extend(p[0], full, x, pos, span, slots, own, 512,
                               "seq.layer17.gqa_a", 17)
        row = gqa.cross_rows(p[1], cross, out[:, -1], pos[:, -1], span,
                             slots, own, 512, "seq.layer19.gqa_cross_a", 19)
        return row, span

    compiled = jax.jit(step, donate_argnums=3).lower(
        p, *placed((jax.ShapeDtypeStruct((8, 4, 2560), jnp.float32),
                    jax.ShapeDtypeStruct((8, 4), i32),
                    jax.ShapeDtypeStruct((17, 33_792, 2560), jnp.bfloat16),
                    jax.ShapeDtypeStruct((8,), i32),
                    jax.ShapeDtypeStruct((8,), i32)))).compile()
    text = compiled.as_text()
    kernels = _kernel_instructions(text)
    scopes = jaxmon.scope_map_of(text)
    assert sorted(scopes[k] for k in kernels) == [
        "seq.layer17.gqa_a.attend", "seq.layer19.gqa_cross_a.attend"]
    assert all("span_walk" in k for k in kernels), kernels
    assert not [i for i, s in scopes.items()
                if s.endswith(".attend") and i.startswith("while")]
    assert "bf16[1,1,512,2560]" not in text
    held = r"bf16\[17,33792,2560\]"
    layouts = set(re.findall(held + r"(\{[^}]*\})", re.sub(
        r"operand_layout_constraints=\{[^=]*\}, ", "", text)))
    assert layouts == {"{2,1,0:T(8,128)(2,1)}"}, layouts
    assert not re.search(rf"= {held}\S* copy\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("path", ["prefill_chunk", "extend"])
def test_a_window_layers_ring_is_left_as_it_is_handed_over(
        one_chip, no_compile_cache, path):
    """A sliding-window layer at MiMo-V2.5's widths (64 query heads of 192 on
    8 key/value heads, values of 128, a window of 128 with a sink; 25 slots,
    rings of 640 rows of 2,560 values): a chunk of 512 positions (the slot's
    rows re-made by a roll and a select) and an extension batch of 8 x 4 (a
    scatter of its real rows). The donated rings keep the layout they arrive
    in and none is copied; the walk's rounds are counted, not unrolled."""
    from predictionio_tpu.ops import gqa

    dims = gqa.GQADims(dim=4096, heads=64, kv_heads=8, head_dim=192,
                       block_len=1, rope_theta=1e4, eps=1e-5, qk_norm=False,
                       v_head_dim=128, rope_dims=64, window=128, sink=True,
                       value_scale=0.707)
    bf16, i32 = jnp.bfloat16, jnp.int32

    def struct(*shape, dtype=bf16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    p = {"w_q": struct(4096, 12288), "w_k": struct(4096, 1536),
         "w_v": struct(4096, 1024), "w_o": struct(8192, 4096),
         "sink": struct(64)}
    assert gqa.ring_len(128, 512) == 640 and dims.cache_width == 2560
    ring = struct(25, 640, 2560)
    if path == "prefill_chunk":
        compiled = jax.jit(
            lambda p, x, n, at, ring, slot: gqa.window_prefill_chunk(
                p, dims, x, n, at, ring, slot), donate_argnums=4).lower(
            p, struct(512, 4096, dtype=jnp.float32), struct(dtype=i32),
            struct(dtype=i32), ring, struct(dtype=i32)).compile()
    else:
        compiled = jax.jit(
            lambda p, x, n, pos, ring, slots: gqa.window_extend(
                p, dims, x, n, pos, ring, slots), donate_argnums=4).lower(
            p, struct(8, 4, 4096, dtype=jnp.float32), struct(8, dtype=i32),
            struct(8, 4, dtype=i32), ring, struct(8, dtype=i32)).compile()
    text = compiled.as_text()
    layouts = set(re.findall(r"bf16\[25,640,2560\](\{[^}]*\})", text))
    assert layouts == {"{2,1,0:T(8,128)(2,1)}"}, layouts
    assert not re.search(r"= bf16\[25,640,2560\]\S* copy\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    assert " while(" in text


@pytest.mark.parametrize("path", ["prefill_chunk", "extend"])
def test_a_recurrent_state_is_stepped_in_place(one_chip, no_compile_cache,
                                               path):
    """One Mamba-2 mixer at the benchmark's widths (hidden 4096, 128 heads of
    64, a state of 128; 33 slots): a chunk of 512 positions of one session,
    and an extension of 16 rows of 4. The donated states keep the buffers
    they arrive in — no copy of the 138 MB of ``S`` — and the chunked scan's
    temporaries stay well under the chip's room."""
    from predictionio_tpu.ops import ssm

    dims = ssm.SSMDims(dim=4096, heads=128, head_dim=64, d_state=128)
    bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32

    def struct(*shape, dtype=bf16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    p = {"w_in": struct(4096, dims.in_width), "conv_w": struct(4, 8448),
         "conv_b": struct(8448), "dt_bias": struct(128, dtype=f32),
         "a_log": struct(128, dtype=f32), "d": struct(128, dtype=f32),
         "norm": struct(8192), "w_out": struct(8192, 4096)}
    state = {"conv": struct(33, 3, 8448),
             "ssm": struct(33, 128, 64, 128, dtype=f32)}
    if path == "prefill_chunk":
        def step(p, a, n_valid, offset, state, slot):
            return ssm.prefill_chunk(p, dims, a, n_valid, offset, state, slot,
                                     "seq.layer0.mamba2_a")
        args = (p, struct(512, 4096, dtype=f32), struct(dtype=i32),
                struct(dtype=i32), state, struct(dtype=i32))
    else:
        def step(p, a, n_new, pos0, state, slots):
            return ssm.extend(p, dims, a, n_new, pos0, state, slots,
                              "seq.layer0.mamba2_a")
        args = (p, struct(16, 4, 4096, dtype=f32), struct(16, dtype=i32),
                struct(16, dtype=i32), state, struct(16, dtype=i32))
    compiled = jax.jit(step, donate_argnums=4).lower(*args).compile()
    text = compiled.as_text()
    assert not re.search(r"= f32\[33,128,64,128\]\S* copy\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 512 << 20
    from predictionio_tpu.obs import jaxmon
    scopes = set(jaxmon.scope_map_of(text).values())
    assert {f"seq.layer0.mamba2_a.ssm.{part}" for part in (
        "in_proj", "conv", "scan", "out_proj")} <= scopes
