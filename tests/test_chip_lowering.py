"""Every Pallas kernel must lower for the TPU — checked from the CPU.

``jax.export`` with ``platforms=["tpu"]`` runs the Pallas→Mosaic lowering
(block-shape rules included) without a chip. It does not run the Mosaic
compiler itself — only ``chip_smoke.py`` on the chip does — but it is the check
that catches an illegal BlockSpec, which is how the flash-2 residual and the
6/12-row LayerNorm once went unnoticed behind interpret-mode tests.
"""

import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.kernels import flash_attention as fa
from bigdl_tpu.kernels import layernorm as ln
from bigdl_tpu.kernels import token_sum as ts

FLASH_KERNELS = ("bigdl_flash_fwd", "bigdl_flash_bwd")


@pytest.fixture
def on_tpu(monkeypatch):
    """Take the kernels' TPU branch (compiled, not interpreted)."""
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    monkeypatch.setattr(ln, "_on_tpu", lambda: True)
    monkeypatch.setattr(ts, "_on_tpu", lambda: True)


def _tpu_module(fn, *avals) -> str:
    return jax.export.export(jax.jit(fn), platforms=["tpu"])(*avals) \
        .mlir_module()


def _kernel_calls(text: str, name: str) -> int:
    return text.count(f'kernel_name = "{name}"')


def _sorts_over(text: str, n: int) -> int:
    """The sorts in ``text`` whose operands are ``n`` long: the routing's over
    every (token, expert) pair, apart from the sums' over a pass's rows."""
    return re.findall(r'"stablehlo\.sort".*?\}\) : \(tensor<(\d+)x', text, re.S) \
        .count(str(n))


# --------------------------------------------------------------- flash
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("shape", [(16, 8, 512, 64), (8, 16, 1024, 64)])
def test_flash_lowers_at_bench_shape(on_tpu, shape, dtype, causal):
    """Forward and the backward kernel at the bench LM's shape and at the
    benchmark's GPT-2 medium cell's."""
    q = jax.ShapeDtypeStruct(shape, dtype)
    fwd = _tpu_module(lambda a, b, c: fa.flash_attention(a, b, c, causal),
                      q, q, q)
    assert _kernel_calls(fwd, "bigdl_flash_fwd") == 1
    grad = _tpu_module(
        jax.grad(lambda a, b, c: fa.flash_attention(a, b, c, causal)
                 .astype(jnp.float32).sum(), argnums=(0, 1, 2)), q, q, q)
    for name in FLASH_KERNELS:
        assert _kernel_calls(grad, name) == 1, name


@pytest.mark.parametrize("t", [8, 24, 64, 384, 640, 8192, 1024, 1280, 2048])
def test_flash_lowers_across_tilings(on_tpu, t):
    """Whole-axis tiles (T <= target) and multiples of 128 beyond it."""
    q = jax.ShapeDtypeStruct((1, 2, t, 64), jnp.bfloat16)
    grad = _tpu_module(
        jax.grad(lambda a, b, c: fa.flash_attention(a, b, c, True)
                 .astype(jnp.float32).sum(), argnums=(0, 1, 2)), q, q, q)
    for name in FLASH_KERNELS:
        assert _kernel_calls(grad, name) == 1, name


@pytest.mark.parametrize("t,target,want", [
    (512, 256, 256), (512, 512, 512), (512, 128, 128),   # the bench LM
    (64, 256, 64), (24, 128, 24), (8, 128, 8),           # whole axis
    (384, 256, 128), (640, 512, 128), (8192, 512, 512),
    (15, 128, None), (4, 128, None),                     # not a sublane multiple
    (200, 128, None), (264, 256, None), (1000, 256, None),  # no 128-multiple
])
def test_pick_block_rule(t, target, want):
    block = fa._pick_block(t, target)
    assert block == want
    if block is not None:
        assert t % block == 0 and (block == t or block % 128 == 0)


@pytest.mark.parametrize("t,d,itemsize,want", [
    (1024, 64, 2, (512, 512, 1024)),      # the GPT-2 medium cell
    (512, 64, 2, (512, 512, 512)), (512, 64, 4, (512, 512, 512)),
    (64, 64, 2, (64, 64, 64)), (24, 16, 4, (24, 24, 24)),  # whole axis
    (384, 64, 2, (384, 384, 384)), (640, 64, 2, (128, 128, 640)),
    (1280, 64, 2, (256, 256, 1280)), (8192, 64, 2, (512, 512, 8192)),
    (8192, 128, 4, (512, 512, 4096)),     # the span gives way to VMEM
    (32768, 128, 4, (512, 512, 4096)), (32768, 64, 2, (512, 512, 16384)),
    (2560, 256, 4, (512, 256, 1280)),     # two spans; chunk and block differ
    (15, 64, 2, None), (200, 64, 2, None), (1000, 64, 4, None),
])
def test_tiles_rule(t, d, itemsize, want):
    tiles = fa._tiles(t, d, itemsize)
    assert tiles == (want and fa._Tiles(*want))
    if tiles is not None:
        block, chunk, span = tiles
        assert t % block == 0 and t % span == 0 and span % chunk == 0
        assert span == 128 or 4 * span * d * itemsize <= fa._RESIDENT_BYTES
        for tile in tiles:
            assert tile == t or tile % 128 == 0


def test_flash_lowers_with_several_spans(on_tpu):
    """A sequence whose keys and values pass the resident budget: the grid
    gets a third axis over spans, and the index maps clamp at the diagonal."""
    q = jax.ShapeDtypeStruct((1, 1, 32768, 128), jnp.float32)
    assert fa._tiles(32768, 128, 4).span < 32768
    grad = _tpu_module(
        jax.grad(lambda a, b, c: fa.flash_attention(a, b, c, True)
                 .sum(), argnums=(0, 1, 2)), q, q, q)
    for name in FLASH_KERNELS:
        assert _kernel_calls(grad, name) == 1, name


@pytest.mark.parametrize("t", [15, 200])
def test_untileable_length_is_reference_by_rule(on_tpu, t):
    """A T no legal tile covers runs the jnp reference by an explicit rule —
    on the TPU branch too, and with no kernel in the program."""
    q = jax.ShapeDtypeStruct((1, 2, t, 8), jnp.float32)
    text = _tpu_module(lambda a, b, c: fa.flash_attention(a, b, c, True),
                       q, q, q)
    assert "tpu_custom_call" not in text


@pytest.mark.parametrize("shape,kv,length,dtype", [
    ((2, 32, 8192, 128), 4, 4096, jnp.bfloat16),    # the SDAR cell
    ((2, 8, 1024, 128), 2, 512, jnp.bfloat16),      # chip_smoke's
    ((1, 4, 2048, 128), 1, 1024, jnp.float32),
    ((1, 4, 128, 128), 1, 64, jnp.float32),         # whole-axis tiles
])
def test_flash_lowers_under_block_diffusion(on_tpu, shape, kv, length, dtype):
    """Forward and the backward kernel with the block-diffusion mask and
    key/value heads at their own count (an index map, nothing repeated)."""
    mask = fa.BlockDiffusion(length, 4)
    q = jax.ShapeDtypeStruct(shape, dtype)
    k = jax.ShapeDtypeStruct((shape[0], kv) + shape[2:], dtype)
    grad = _tpu_module(
        jax.grad(lambda a, b, c: fa.flash_attention(a, b, c, False, None, mask)
                 .astype(jnp.float32).sum(), argnums=(0, 1, 2)), q, k, k)
    for name in FLASH_KERNELS:
        assert _kernel_calls(grad, name) == 1, name
    assert "repeat" not in grad


@pytest.mark.parametrize("shape,kv,size,dtype", [
    ((1, 28, 16384, 128), 4, 4096, jnp.bfloat16),   # the SmallThinker cell: two spans
    ((1, 28, 16384, 128), 4, 4096, jnp.float32),    # four spans of 4,096
    ((2, 8, 2048, 128), 2, 300, jnp.bfloat16),      # a window under one chunk
    ((1, 4, 128, 64), 1, 16, jnp.float32),          # whole-axis tiles
])
def test_flash_lowers_under_a_causal_window(on_tpu, shape, kv, size, dtype):
    """Forward and the backward kernel with the windowed mask and key/value
    heads at their own count, at the SmallThinker cell's shape among them:
    28 query heads on 4 key/value heads of 128 over 16,384 positions."""
    mask = fa.CausalWindow(size)
    q = jax.ShapeDtypeStruct(shape, dtype)
    k = jax.ShapeDtypeStruct((shape[0], kv) + shape[2:], dtype)
    tiles = fa._tiles_under(mask, shape[2], shape[3], jnp.dtype(dtype).itemsize)
    if shape[2] == 16384:
        assert tiles == fa._Tiles(512, 512, 8192 if dtype == jnp.bfloat16 else 4096)
    grad = _tpu_module(
        jax.grad(lambda a, b, c: fa.flash_attention(a, b, c, False, None, mask)
                 .astype(jnp.float32).sum(), argnums=(0, 1, 2)), q, k, k)
    for name in FLASH_KERNELS:
        assert _kernel_calls(grad, name) == 1, name
    assert "repeat" not in grad


# ------------------------- the backward kernel built for a described v5e
@pytest.fixture(scope="module")
def one_v5e_chip():
    """A chip that is described, not attached: the TPU's compiler builds the
    kernel for it from here (no result, no time). Skips where this process
    cannot describe one."""
    import os

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("shape,kv,mask,tiles,limit_mib", [
    ((8, 16, 1024, 64), 16, "causal", (512, 512, 1024), 32),          # gpt2-medium.train-t1024
    ((2, 32, 8192, 128), 4, fa.BlockDiffusion(4096, 4), (512, 512, 8192), 34),   # sdar-30b-a3b.train-bd4k
    ((1, 28, 16384, 128), 4, "causal", (512, 512, 8192), 42),         # smallthinker-21b-a3b.train-t16k,
    ((1, 28, 16384, 128), 4, fa.CausalWindow(4096), (512, 512, 8192), 42),   # its full and its windowed layers
])
def test_flash_backward_builds_within_the_vmem_it_asks_for(
        one_v5e_chip, shape, kv, mask, tiles, limit_mib):
    """The three cells' shapes through Mosaic itself: the plan of scratch and
    blocks comes from the shapes (``_bwd_vmem_limit``: the forward's 32 MiB
    where the plan needs less), stays under the ceiling, and the compiler,
    which refuses a kernel that passes its limit, builds it."""
    b, h, t, d = shape
    assert fa._tiles_under(mask, t, d, 2) == fa._Tiles(*tiles)
    limit = fa._bwd_vmem_limit(t, d, 2, fa._Tiles(*tiles))
    assert limit >> 20 == limit_mib and limit <= fa._VMEM_CEILING_BYTES
    struct = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_v5e_chip)
    q, k = struct(b * h, t, d), struct(b * kv, t, d)
    row = struct(b * h, 1, t, dtype=jnp.float32)
    text = fa._pallas_flash_bwd.lower(
        q, k, k, q, row, row, mask=mask, tiles=fa._Tiles(*tiles),
        interpret=False).compile().as_text()
    assert "bigdl_flash_bwd" in text


@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
def test_latent_attentions_kernels_build_at_the_cells_shape(one_v5e_chip, kernel):
    """``deepseek-v2-lite.train-t16k``'s attention through Mosaic: 16 heads,
    T 16,384, the query/key head 192 and the value head 128 each at its own
    width (nothing padded), YaRN's softmax scale. The backward asks for 48 MiB:
    spans of 4,096 queries and head-long sums of dk at 192 (256 lanes) and of
    dv at 128."""
    t, d, d_v, scale = 16384, 192, 128, 0.114721
    tiles = fa._tiles_under("causal", t, d, 2, d_v)
    assert tiles == fa._Tiles(512, 512, 4096)
    assert fa._bwd_vmem_limit(t, d, 2, tiles, d_v) >> 20 == 48
    struct = lambda *dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_v5e_chip)
    qk, v = struct(16, t, d), struct(16, t, d_v)
    if kernel == "fwd":
        text = fa._pallas_flash_call.lower(
            qk, qk, v, mask="causal", tiles=tiles, interpret=False,
            scale=scale).compile().as_text()
    else:
        row = struct(16, 1, t, dtype=jnp.float32)
        text = fa._pallas_flash_bwd.lower(
            qk, qk, v, v, row, row, mask="causal", tiles=tiles, interpret=False,
            scale=scale).compile().as_text()
    assert f"bigdl_flash_{kernel}" in text


def test_a_sequence_whose_sums_pass_the_ceiling_is_refused_by_name():
    """dk and dv sum in VMEM as long as the head: at T 131,072 and head_dim
    128 that is 128 MiB, and the plan says so before the compiler would. Half
    of that length still fits."""
    assert fa._bwd_vmem_limit(65536, 128, 2, fa._tiles(65536, 128, 2)) >> 20 == 90
    with pytest.raises(ValueError, match="T=131072"):
        fa._bwd_vmem_limit(131072, 128, 2, fa._tiles(131072, 128, 2))


@pytest.mark.parametrize("rows,half_held", [(32768, False), (131072, False), (32768, True)])
def test_grouped_matmul_lowers_at_the_sdar_shapes(monkeypatch, rows, half_held):
    """The grouped products of the SDAR cell's expert layer: a pass's 32,768
    rows (twice the balanced expectation of 16,384 positions x 8 over 16 of
    128 experts) and every pair there is, 16 held experts, gate and up side by
    side, then down; forward and the gradients for rows and matrices. And a
    pass whose sizes leave half its rows to no group, as at the expectation."""
    from bigdl_tpu.kernels import grouped_matmul as gm
    monkeypatch.setattr(gm, "_on_tpu", lambda: True)
    x = jax.ShapeDtypeStruct((rows, 2048), jnp.bfloat16)
    w_in = jax.ShapeDtypeStruct((16, 2048, 1536), jnp.bfloat16)
    w_out = jax.ShapeDtypeStruct((16, 768, 2048), jnp.bfloat16)
    sizes = jax.ShapeDtypeStruct((16,), jnp.int32)

    def experts(x, a, b, n):
        if half_held:       # 16 groups of 1,024 rows: the last 16,384 in none
            n = jnp.full((16,), rows // 32, jnp.int32)
        h = gm.grouped_matmul(x, a, n)
        return gm.grouped_matmul(jax.nn.silu(h[:, :768]) * h[:, 768:], b, n) \
            .astype(jnp.float32).sum()

    text = _tpu_module(jax.grad(experts, argnums=(0, 1, 2)), x, w_in, w_out, sizes)
    assert text.count("tpu_custom_call") >= 5       # the first forward, 2 + 2 backward


def test_routed_layer_lowers_at_the_sdar_shapes(monkeypatch):
    """The whole routed layer as the SDAR cell runs it (16,384 positions, top-8
    of 128, experts 48-63 held): the bounded pass and the loop that repeats it,
    forward and the hand-written VJP. The kernels see a pass's 32,768 rows."""
    from bigdl_tpu.kernels import grouped_matmul as gm
    from bigdl_tpu.parallel.moe import MoE
    monkeypatch.setattr(gm, "_on_tpu", lambda: True)
    layer = MoE(2048, 768, 128, router="topk", top_k=8, held=(48, 16))
    params = {k: jax.ShapeDtypeStruct(v.shape, jnp.bfloat16)
              for k, v in layer.get_params().items()}
    x = jax.ShapeDtypeStruct((16384, 2048), jnp.bfloat16)

    def loss(p, x):
        return layer.apply(p, layer.get_state(), x)[0].astype(jnp.float32).sum()

    for fn, calls in ((loss, 2), (jax.grad(loss, argnums=(0, 1)), 6)):
        text = _tpu_module(fn, params, x)
        assert text.count("tpu_custom_call") >= calls
        assert "stablehlo.while" in text            # the passes after the first
        assert "32768x2048xbf16" in text and "131072x2048" not in text


def test_the_mamba_mixer_and_the_ungated_latent_experts_lower_at_the_cells_shapes(
        on_tpu, monkeypatch):
    """The Nemotron cell's two new paths at its shapes, forward and gradient:
    Mamba-2's mixer over 8,192 positions at 16 heads of 64, one group, a
    state of 128 in chunks of 128 (plain XLA, under its two scopes); and the
    expert layer with the sigmoid router over 512 experts (22 a token, 8
    held), ReLU squared experts of 2,688 in the 1,024 latent (one input
    matrix: the first grouped product is 1,024 x 2,688) and the shared expert.
    The grouped products see a pass's 5,632 rows of the latent."""
    from bigdl_tpu.kernels import grouped_matmul as gm
    from bigdl_tpu.nn import Mamba2Mixer
    from bigdl_tpu.parallel.moe import MoE
    monkeypatch.setattr(gm, "_on_tpu", lambda: True)
    bf16 = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16), tree)
    x = jax.ShapeDtypeStruct((1, 8192, 4096), jnp.bfloat16)
    mixer = Mamba2Mixer(4096, 16, 64, 1, 128, 4, 128, 1e-5)

    def mamba(p, x):
        return mixer.apply(p, {}, x)[0].astype(jnp.float32).sum()

    text = _tpu_module(jax.grad(mamba, argnums=(0, 1)), bf16(mixer.get_params()), x)
    assert "bigdl_mamba" in text and "bigdl_ssm_scan" in text
    assert "1x64x128x1x16x64xbf16" in text     # a chunk's scores times dt x, by head
    layer = MoE(4096, 2688, 512, router="topk", top_k=22, held=(216, 8), gate="relu2",
                shared=5376, scoring="sigmoid", routed_scale=5.0, latent=1024)
    assert layer.get_params()["w_in"].shape == (8, 1024, 2688)
    state = layer.get_state()

    def experts(p, x):
        return layer.apply(p, state, x)[0].astype(jnp.float32).sum()

    text = _tpu_module(jax.grad(experts, argnums=(0, 1)), bf16(layer.get_params()), x)
    assert text.count("tpu_custom_call") >= 6       # gmm twice forward, four backward
    assert "5632x1024xbf16" in text and "bigdl_moe_latent" in text


# ----------------------------------------------------- the sum by token
@pytest.mark.parametrize("weighted", [True, False])
@pytest.mark.parametrize("rows,d", [(32768, 2048), (24576, 2560), (24576, 2048)])
def test_the_sum_by_token_lowers_at_the_cells_shapes(on_tpu, rows, d, weighted):
    """``bigdl_moe_sum`` over a pass's bf16 rows of the SDAR, SmallThinker and
    DeepSeek cells (16,384 positions each), weighted as the output and not as
    the input's gradient: one kernel, and no tensor of the pass's rows in fp32
    (the scatter-add it replaced made one)."""
    args = [jax.ShapeDtypeStruct((rows, d), jnp.bfloat16),
            jax.ShapeDtypeStruct((rows,), jnp.int32),
            jax.ShapeDtypeStruct((rows,), jnp.bool_)]
    if weighted:
        args.append(jax.ShapeDtypeStruct((rows,), jnp.float32))
    text = _tpu_module(lambda r, t, l, *w: ts.sum_by_token(r, t, l, 16384, *w), *args)
    assert _kernel_calls(text, ts.NAME) == 1
    assert f"{rows}x{d}xf32" not in text


@pytest.mark.parametrize("tokens,rows,d,itemsize,want", [
    (16384, 24576, 2560, 2, (128, 128, 2560)),      # the SmallThinker cell
    (16384, 32768, 2048, 2, (128, 128, 2048)),      # the SDAR cell
    (96, 192, 64, 4, (96, 128, 64)),                # whole axes, a width under a lane tile
    (5, 8, 64, 4, (5, 8, 64)),
    (16384, 24576, 16384, 4, (128, 128, 8192)),     # rows too wide for the budget
])
def test_the_sum_tiles_rule(tokens, rows, d, itemsize, want):
    tiles = ts._tiles(tokens, rows, d, itemsize)
    assert tiles == ts._Tiles(*want)
    assert d % tiles.width == 0
    assert ts._vmem_bytes(*tiles, itemsize) <= ts._BUDGET_BYTES


def _mosaic_lowerings(monkeypatch) -> collections.Counter:
    """How often each Pallas kernel is lowered to Mosaic, by its name: counted
    in the lowering rule itself."""
    from jax._src.pallas.mosaic import pallas_call_registration as registration
    counts = collections.Counter()
    rule = registration.pallas_call_tpu_lowering_rule

    def counted(ctx, *args, **params):
        counts[params["name"]] += 1
        return rule(ctx, *args, **params)
    monkeypatch.setattr(registration, "pallas_call_tpu_lowering_rule", counted)
    return counts


@pytest.mark.parametrize("remat", [True, False])
def test_a_period_of_four_lowers_the_sum_once_a_weighting(on_tpu, monkeypatch, remat):
    """The guard on set-up. Eight routed layers in two periods of four, the
    period written out in the scan body: the step calls the sum by token from
    16 places or more (four layers, forward and backward, each the first pass
    and the loop's), and lowers ``bigdl_moe_sum`` to Mosaic twice, weighted
    and not. A warm run lowers its program before the compile cache answers,
    so a lowering a place would be paid in every run."""
    lowered = _mosaic_lowerings(monkeypatch)
    text = _decoder_gradient(monkeypatch, remat, **PERIOD)
    assert len(re.findall(r"call @_sum(_\d+)?\(", text)) >= 16
    assert lowered[ts.NAME] == 2


def test_a_sum_lowered_at_every_place_would_show(on_tpu, monkeypatch):
    """The control: with the sum not jitted the same count reads a lowering
    for each place that calls it."""
    monkeypatch.setattr(ts, "_sum", ts._sum.__wrapped__)
    lowered = _mosaic_lowerings(monkeypatch)
    _decoder_gradient(monkeypatch, False, **PERIOD)
    assert lowered[ts.NAME] >= 16


def test_the_sum_by_token_builds_at_the_smallthinker_shape(one_v5e_chip):
    """Through Mosaic itself at the SmallThinker cell's pass: 24,576 bf16
    rows of 2,560 into 16,384 tokens, weighted (three products a pair), with
    the sort and the gather before it."""
    struct = lambda *dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_v5e_chip)
    text = ts._sum.lower(
        struct(24576, 2560, dtype=jnp.bfloat16), struct(24576, dtype=jnp.int32),
        struct(24576, dtype=jnp.bool_), struct(24576, dtype=jnp.float32),
        tokens=16384, interpret=False).compile().as_text()
    assert ts.NAME in text


# ----------------------------------------------------------- layer norm
@pytest.mark.parametrize("h", [64, 512])
@pytest.mark.parametrize("rows", [1, 5, 6, 12, 13, 24, 300, 8192])
def test_layer_norm_lowers(on_tpu, rows, h):
    g = jax.ShapeDtypeStruct((h,), jnp.float32)
    for dtype in (jnp.float32, jnp.bfloat16):
        x = jax.ShapeDtypeStruct((rows, h), dtype)
        text = _tpu_module(lambda a, b, c: ln.fused_layer_norm(a, b, c),
                           x, g, g)
        assert _kernel_calls(text, "bigdl_layer_norm") == 1


@pytest.mark.parametrize("rows,h,itemsize,want", [
    (6, 512, 4, 6), (12, 512, 4, 12), (13, 512, 2, 13),   # whole array
    (8192, 512, 4, 256), (300, 64, 2, 256),
    (8192, 8192, 4, 64), (8192, 8192, 2, 64),             # wide rows: VMEM
    (8192, 1 << 20, 2, 16), (8192, 1 << 20, 4, 8),        # never below a tile
])
def test_row_block_rule(rows, h, itemsize, want):
    block = ln._row_block(rows, h, itemsize)
    assert block == want
    sublane = 8 * (4 // itemsize)
    assert block == rows or block % sublane == 0


@pytest.mark.parametrize("rows", [6, 13, 300])
def test_layer_norm_ragged_rows_match_reference(rows):
    """Whole-array blocks and an overhanging last block (300 = 256 + 44),
    through the interpreter."""
    rng = np.random.default_rng(rows)
    x = jnp.asarray(rng.normal(size=(rows, 64)).astype(np.float32))
    g = jnp.asarray(rng.normal(size=(64,)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(64,)).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(ln.fused_layer_norm(x, g, b, 1e-5, True)),
        np.asarray(ln._reference_layer_norm(x, g, b, 1e-5)),
        rtol=1e-5, atol=1e-5)


def test_layer_norm_kernel_keeps_the_reference_dtype():
    """bf16 activations under fp32 gamma/beta: the kernel must return what
    the reference returns (fp32), or the reference-VJP backward rejects the
    cotangent — found on the chip, where the kernel is the default path."""
    x = jnp.ones((16, 32), jnp.bfloat16)
    g, b = jnp.ones((32,), jnp.float32), jnp.zeros((32,), jnp.float32)
    ref = ln._reference_layer_norm(x, g, b, 1e-5)
    assert ln.fused_layer_norm(x, g, b, 1e-5, True).dtype == ref.dtype
    dx = jax.grad(lambda a: ln.fused_layer_norm(a, g, b, 1e-5, True)
                  .astype(jnp.float32).sum())(x)
    assert dx.dtype == x.dtype and dx.shape == x.shape


# ------------------------------------------------------ under shard_map
def _mesh_2x2():
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "pipe"))


def test_layer_norm_lowers_inside_shard_map(on_tpu):
    """Pipeline stages run LayerNorm inside shard_map, where pallas_call must
    declare which mesh axes its output varies over (``out_struct``). Found on
    four real chips: until then the silent fallback had hidden it."""
    from jax.sharding import PartitionSpec as P
    f = jax.shard_map(lambda x, g, b: ln.fused_layer_norm(x, g, b),
                      mesh=_mesh_2x2(), in_specs=(P("data"), P(), P()),
                      out_specs=P("data"))
    x = jax.ShapeDtypeStruct((8, 16, 32), jnp.float32)
    g = jax.ShapeDtypeStruct((32,), jnp.float32)
    assert _kernel_calls(_tpu_module(f, x, g, g), "bigdl_layer_norm") == 1
    # the backward is the reference VJP: it only has to trace and lower
    _tpu_module(jax.grad(lambda x, g, b: jnp.square(f(x, g, b)).sum(),
                         argnums=(0, 1)), x, g, g)


def test_flash_lowers_inside_shard_map(on_tpu):
    from jax.sharding import PartitionSpec as P
    f = jax.shard_map(lambda q, k, v: fa.flash_attention(q, k, v, True),
                      mesh=_mesh_2x2(), in_specs=(P("data"),) * 3,
                      out_specs=P("data"))
    q = jax.ShapeDtypeStruct((4, 2, 16, 8), jnp.float32)
    grad = _tpu_module(jax.grad(lambda a, b, c: f(a, b, c).sum(),
                                argnums=(0, 1, 2)), q, q, q)
    for name in FLASH_KERNELS:
        assert _kernel_calls(grad, name) == 1, name


# ------------------------------------------------- no fallback on TPU
class _Boom(RuntimeError):
    pass


def _boom(*a, **k):
    raise _Boom("kernel build failed")


def _qkv(t=16):
    return (jnp.ones((1, 2, t, 8), jnp.float32),) * 3


def test_flash_forward_build_error_raises_on_tpu(on_tpu, monkeypatch):
    monkeypatch.setattr(fa, "_pallas_flash_call", _boom)
    with pytest.raises(_Boom):
        fa.flash_attention(*_qkv(), True)


def test_flash_backward_build_error_raises(monkeypatch):
    # forward through the interpreter (force_pallas=True off-TPU), then the
    # backward kernel fails to build: the reference VJP must not take over
    monkeypatch.setattr(fa, "_pallas_flash_bwd", _boom)
    with pytest.raises(_Boom):
        jax.grad(lambda a, b, c: fa.flash_attention(a, b, c, True, True)
                 .sum(), argnums=(0, 1, 2))(*_qkv())


def test_layer_norm_build_error_raises_on_tpu(on_tpu, monkeypatch):
    monkeypatch.setattr(ln, "_pallas_layer_norm", _boom)
    with pytest.raises(_Boom):
        ln.fused_layer_norm(jnp.ones((6, 32)), jnp.ones((32,)),
                            jnp.zeros((32,)))


def test_off_tpu_default_is_the_reference():
    """Off TPU nothing changes: force_pallas=None computes the reference,
    bit for bit."""
    q, k, v = _qkv()
    np.testing.assert_array_equal(
        np.asarray(fa.flash_attention(q, k, v, True)),
        np.asarray(fa._reference_attention(q, k, v, True)))
    x, g, b = jnp.ones((6, 32)) * 2, jnp.ones((32,)), jnp.zeros((32,))
    np.testing.assert_array_equal(
        np.asarray(ln.fused_layer_norm(x, g, b)),
        np.asarray(ln._reference_layer_norm(x, g, b, 1e-5)))


# ------------------------------------- what the scanned decoder keeps
def _decoder_gradient(monkeypatch, remat, body=None, **kinds) -> str:
    """The gradient of a two-layer ``ConfigDecoder`` under ``BlockDiffusion``
    (or, with ``kinds``, of a causal one of eight layers of those kinds),
    lowered for the TPU. ``body`` wraps the scan's layer in ``remat``'s place."""
    from bigdl_tpu.kernels import grouped_matmul as gm
    from bigdl_tpu.models.transformerlm import ConfigDecoder
    monkeypatch.setattr(gm, "_on_tpu", lambda: True)
    monkeypatch.setattr(ts, "_on_tpu", lambda: True)
    model = ConfigDecoder(
        vocab_size=512, hidden_size=256, num_hidden_layers=8 if kinds else 2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=128,
        moe_intermediate_size=128, num_experts=16, num_experts_per_tok=4,
        held=(4, 4), block_diffusion=None if kinds else (512, 4), remat=remat,
        **kinds)
    if body is not None:
        scan = jax.lax.scan
        monkeypatch.setattr(jax.lax, "scan",
                            lambda f, *a, **k: scan(body(f), *a, **k))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16), model.get_params())

    def loss(p, x):
        out, _ = model.apply(p, model.get_state(), x, training=True)
        return out[1].astype(jnp.float32).sum()

    return _tpu_module(jax.grad(loss), params,
                       jax.ShapeDtypeStruct((2, 1024), jnp.int32))


@pytest.mark.parametrize("remat", [True, False])
def test_scanned_decoder_runs_attention_and_routing_once(on_tpu, monkeypatch, remat):
    """Rematerialised or not, the step holds each flash kernel once (the scan
    body's forward, the backward body's one), one top-k and one sort of the
    2,048 positions' 8,192 pairs: what ``ConfigDecoder``'s policy keeps is not
    run again in the backward pass. (The sums by token sort a pass's rows.)"""
    text = _decoder_gradient(monkeypatch, remat)
    for name in FLASH_KERNELS:
        assert _kernel_calls(text, name) == 1, name
    assert _sorts_over(text, 8192) == 1
    assert text.count("@mhlo.topk") == 1


#: eight layers in two periods of (full, window, window, window)
PERIOD = dict(sliding_window_layout=[0, 1, 1, 1] * 2, sliding_window_size=256,
              rope_layout=[0, 1, 1, 1] * 2, router_input="layer",
              expert_gate="relu", qk_norm=False)


@pytest.mark.parametrize("remat", [True, False])
def test_a_period_of_four_holds_each_kind_once_a_layer(on_tpu, monkeypatch, remat):
    """Eight layers in two periods of (full, window, window, window): the scan
    body holds the period written out, four layers each with its own static
    mask and none that computes two kinds: every flash kernel once under the
    causal mask and three times under the window (forward body and backward
    body), four top-k and four sorts (kept, not run again), one scan over two
    periods."""
    text = _decoder_gradient(monkeypatch, remat, **PERIOD)
    for jitted in ("_pallas_flash_call", "_pallas_flash_bwd"):
        assert len(re.findall(rf"call @{jitted}(_\d+)?\(", text)) == 4, jitted
    # two masks and no more: the kernels' bodies as lowered (a body that
    # several layers share is lowered once)
    assert 2 <= _kernel_calls(text, "bigdl_flash_bwd") <= 4
    if remat:       # (without it the sort is one shared function, called four times)
        assert _sorts_over(text, 8192) == 4
        assert text.count("@mhlo.topk") == 4


def test_whole_layer_rematerialisation_would_show(on_tpu, monkeypatch):
    """The control: with a bare ``jax.checkpoint`` around the layer the same
    count reads two, so the test above would see the policy go."""
    text = _decoder_gradient(monkeypatch, False, body=jax.checkpoint)
    assert _kernel_calls(text, "bigdl_flash_fwd") == 2
    assert _kernel_calls(text, "bigdl_flash_bwd") == 1
    assert _sorts_over(text, 8192) == 2
    assert text.count("@mhlo.topk") == 2


def test_the_latent_decoders_step_holds_the_kernels_and_its_scopes(on_tpu, monkeypatch):
    """A decoder of DeepSeek-V2's kinds at its head widths (query/key 128 + 64,
    value 128, YaRN): one leading dense layer, then two routed layers with
    shared experts under the scan. Its lowered gradient runs attention in the
    flash kernels, forward and backward, once for the dense layer and once in
    the scan's body (each kept by the policy, not run again), and carries the
    three scopes the per-layer metrics read."""
    from bigdl_tpu.kernels import grouped_matmul as gm
    from bigdl_tpu.models.transformerlm import ConfigDecoder
    monkeypatch.setattr(gm, "_on_tpu", lambda: True)
    yarn = {"type": "yarn", "factor": 40, "original_max_position_embeddings": 4096,
            "beta_fast": 32, "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707}
    model = ConfigDecoder(
        vocab_size=512, hidden_size=256, num_hidden_layers=3, num_attention_heads=2,
        num_key_value_heads=2, moe_intermediate_size=128, num_experts=16,
        num_experts_per_tok=4, held=(4, 4), qk_norm=False, kv_lora_rank=128,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, rope_scaling=yarn,
        first_k_dense_replace=1, intermediate_size=256, n_shared_experts=2)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16), model.get_params())

    def loss(p, x):
        out, _ = model.apply(p, model.get_state(), x, training=True)
        return out[1].astype(jnp.float32).sum()

    text = _tpu_module(jax.grad(loss), params, jax.ShapeDtypeStruct((2, 1024), jnp.int32))
    for jitted in ("_pallas_flash_call", "_pallas_flash_bwd"):
        assert len(re.findall(rf"call @{jitted}(_\d+)?\(", text)) == 2, jitted
    for name in FLASH_KERNELS:
        assert _kernel_calls(text, name) >= 1, name
    for scope in ("bigdl_attn_latent", "bigdl_moe_shared", "bigdl_mlp_dense"):
        assert scope in text, scope
