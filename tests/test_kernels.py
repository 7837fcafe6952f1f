"""Per-kernel validation: Pallas (interpret backend) vs the pure-jnp oracle,
swept over shapes and bit-widths via the unified kernel API."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import api, ref
from repro.kernels.api import PrecisionSpec, SlicedTensor, use_backend
from repro.models.common import quantize_weight


@pytest.mark.parametrize("xb,wb", [(8, 8), (4, 4), (16, 8), (8, 16), (16, 16)])
@pytest.mark.parametrize("mnk", [(128, 128, 128), (256, 128, 256), (128, 256, 512)])
def test_bitslice_matmul_matches_wide_int(xb, wb, mnk):
    m, n, k = mnk
    rng = np.random.default_rng(xb * 100 + wb + m)
    xlo, xhi = ref.slice_range(xb)
    wlo, whi = ref.slice_range(wb)
    x = jnp.asarray(rng.integers(xlo, xhi + 1, (m, k)), jnp.int32)
    w = jnp.asarray(rng.integers(wlo, whi + 1, (k, n)), jnp.int32)
    xs, ws = SlicedTensor.from_int(x, xb), SlicedTensor.from_int(w, wb)
    assert (xs.to_int() == x).all(), "x slice roundtrip"
    assert (ws.to_int() == w).all(), "w slice roundtrip"
    want = ref.int_matmul_wide_ref(x, w, xb, wb)
    with use_backend("xla"):
        got_ref = api.matmul(xs, ws)
    with use_backend("interpret"):
        got_pal = api.matmul(xs, ws, block=(128, 128, 128))
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got_ref))
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got_pal))


def test_zero_slice_skipping_exact():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(-100, 100, (128, 256)), jnp.int32)
    w = jnp.asarray(rng.integers(-100, 100, (256, 128)), jnp.int32)
    xs = SlicedTensor.from_int(x, 8)
    ws = SlicedTensor.from_int(w, 16)
    assert ws.zero_slices, "small-valued int16 weights must have a dead hi slice"
    assert api.skip_pairs(xs, ws), "dead slice must induce skip pairs"
    want = ref.int_matmul_wide_ref(x, w, 8, 16)
    with use_backend("interpret"):
        got = api.matmul(xs, ws, block=(128, 128, 128))
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
@pytest.mark.parametrize("n,d", [(8, 512), (64, 512), (256, 1024)])
def test_htree_reduce_matches_tree_oracle(dtype, n, d):
    x = jax.random.normal(jax.random.key(n + d), (n, d), jnp.float32)
    if dtype == jnp.int32:
        x = (x * 100).astype(jnp.int32)
    else:
        x = x.astype(dtype)
    want = ref.htree_reduce_ref(x)
    with use_backend("interpret"):
        got = api.htree_reduce(x)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


@pytest.mark.parametrize("b,t,w", [(1, 256, 512), (2, 512, 1024), (3, 128, 512)])
@pytest.mark.slow
def test_rglru_scan_kernel(b, t, w):
    ks = jax.random.split(jax.random.key(b * t), 3)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (b, t, w)))
    bb = jax.random.normal(ks[1], (b, t, w))
    h0 = jax.random.normal(ks[2], (b, w))
    want = ref.rglru_scan_ref(a, bb, h0)
    with use_backend("interpret"):
        got = api.rglru_scan(a, bb, h0)
    np.testing.assert_allclose(np.asarray(want), np.asarray(got), atol=1e-4, rtol=1e-4)


def test_quantized_matmul_end_to_end_error_bound():
    ks = jax.random.split(jax.random.key(7), 2)
    x = jax.random.normal(ks[0], (64, 256), jnp.float32)
    w = jax.random.normal(ks[1], (256, 128), jnp.float32) * 0.05
    q = quantize_weight(w, 8)
    out = api.quantized_matmul(
        x, q["w_q"].astype(jnp.int32), q["w_scale"][0], PrecisionSpec.int8
    )
    rel = float(jnp.abs(out - x @ w).max() / jnp.abs(x @ w).max())
    assert rel < 0.05, rel


# ---------------------------------------------------------------------------
# shapes and values the TPU forces: padded blocks, lane-tiled 1-D ops, the
# int32 matmuls on int8 slices (interpret mode runs the same kernel bodies
# and block specs the chip compiles)
# ---------------------------------------------------------------------------

_I32 = (np.iinfo(np.int32).min, np.iinfo(np.int32).max)


def _near_wrap(shape, seed):
    """int32 values within 2^12 of either end of the range."""
    rng = np.random.default_rng(seed)
    off = rng.integers(0, 1 << 12, shape)
    return jnp.asarray(np.where(rng.random(shape) < 0.5, _I32[0] + off, _I32[1] - off),
                       jnp.int32)


def _full(shape, seed, lo=_I32[0], hi=_I32[1]):
    return jnp.asarray(np.random.default_rng(seed).integers(lo, hi, shape), jnp.int32)


_AWKWARD = {
    # 1000-class head: N padded to 1024 columns
    "int_matmul_head_n1000": lambda: (
        "int_matmul", (_full((24, 512), 1, -(1 << 11), 1 << 11), _full((512, 1000), 2, -3, 4)),
        {"x_bits": 12, "w_bits": 3}),
    "int_matmul_near_wrap": lambda: (
        "int_matmul", (_near_wrap((40, 300), 3), _near_wrap((300, 130), 4)), {}),
    "conv2d_near_wrap": lambda: (
        "conv2d", (_near_wrap((2, 5, 9, 9), 5), _near_wrap((7, 5, 3, 3), 6)),
        {"stride": 2, "padding": 1}),
    # a hinted operand at the top of its range (32767 needs a third slice)
    "conv2d_hint_edge": lambda: (
        "conv2d", (jnp.full((1, 2, 6, 6), 32767, jnp.int32), _full((3, 2, 3, 3), 7, -3, 4)),
        {"stride": 1, "padding": 1, "x_bits": 16, "w_bits": 3}),
    # conv2d's two paths (``kernels.conv``): taps for C >= 64 or a 1x1 kernel,
    # a patch slab for narrower C; 1, 2 and 4 digits (x_bits 8, 16, none)
    "conv2d_taps_s1_p1_odd9_x16": lambda: (
        "conv2d", (_full((2, 64, 9, 9), 25, -(1 << 15), 1 << 15),
                   _full((16, 64, 3, 3), 26, -128, 128)),
        {"stride": 1, "padding": 1, "x_bits": 16, "w_bits": 8}),
    "conv2d_taps_s1_p0_odd7_near_wrap": lambda: (
        "conv2d", (_near_wrap((2, 64, 7, 7), 27), _near_wrap((8, 64, 3, 3), 28)),
        {"stride": 1, "padding": 0}),
    "conv2d_taps_s2_p1_odd7_near_wrap": lambda: (
        "conv2d", (_near_wrap((2, 64, 7, 7), 29), _full((8, 64, 3, 3), 30, -128, 128)),
        {"stride": 2, "padding": 1, "w_bits": 8}),
    "conv2d_taps_s2_p1_even8_x8": lambda: (
        "conv2d", (_full((1, 72, 8, 8), 31, -128, 128), _full((24, 72, 3, 3), 32, -128, 128)),
        {"stride": 2, "padding": 1, "x_bits": 8, "w_bits": 8}),
    "conv2d_taps_1x1_s2_odd9_x8": lambda: (
        "conv2d", (_full((2, 64, 9, 9), 33, -128, 128), _full((24, 64, 1, 1), 34, -128, 128)),
        {"stride": 2, "padding": 0, "x_bits": 8, "w_bits": 8}),
    "conv2d_taps_1x1_s1_c3_near_wrap": lambda: (
        "conv2d", (_near_wrap((2, 3, 7, 7), 35), _near_wrap((5, 3, 1, 1), 36)),
        {"stride": 1, "padding": 0}),
    # 2 x 35 x 35 grid rows at 4 digits of 64 channels: two row blocks that
    # do not divide it, each with its halo
    "conv2d_taps_two_row_blocks": lambda: (
        "conv2d", (_near_wrap((2, 64, 33, 33), 37), _full((8, 64, 3, 3), 38, -128, 128)),
        {"stride": 1, "padding": 1, "w_bits": 8}),
    # C = 640 > 512: the channel sweep accumulates over two C blocks
    "conv2d_taps_c640_sweep": lambda: (
        "conv2d", (_near_wrap((1, 640, 5, 5), 39), _full((8, 640, 3, 3), 40, -128, 128)),
        {"stride": 1, "padding": 1, "w_bits": 8}),
    "conv2d_patches_c3_s1_p1_x8": lambda: (
        "conv2d", (_full((2, 3, 9, 9), 41, -128, 128), _full((16, 3, 3, 3), 42, -128, 128)),
        {"stride": 1, "padding": 1, "x_bits": 8, "w_bits": 8}),
    "conv2d_patches_c3_s2_p0_odd9_near_wrap": lambda: (
        "conv2d", (_near_wrap((2, 3, 9, 9), 43), _near_wrap((8, 3, 3, 3), 44)),
        {"stride": 2, "padding": 0}),
    "conv2d_patches_c3_s2_p1_odd7_x16": lambda: (
        "conv2d", (_full((1, 3, 7, 7), 45, -(1 << 15), 1 << 15),
                   _full((8, 3, 3, 3), 46, -128, 128)),
        {"stride": 2, "padding": 1, "x_bits": 16, "w_bits": 8}),
    "attention_qk_near_wrap": lambda: (
        "attention_qk", (_near_wrap((5, 64), 8), _near_wrap((37, 64), 9)), {}),
    "attention_pv_full_range": lambda: (
        "attention_pv", (_full((5, 37), 10, 0, 65), _full((37, 64), 11)), {}),
    "decode_gemv_near_wrap": lambda: (
        "decode_gemv", (_near_wrap((1000, 96), 12), _near_wrap((96,), 13)), {}),
    "ewise_add_945_wraps": lambda: (
        "ewise_add", (_near_wrap((3, 5, 7, 9), 14), _near_wrap((3, 5, 7, 9), 15)), {}),
    "relu_945_int8": lambda: (
        "relu", (_full((3, 5, 7, 9), 16, -128, 128).astype(jnp.int8),), {}),
    "maxpool2d_150_windows": lambda: (
        "maxpool2d", (_near_wrap((2, 3, 10, 10), 17),), {"window": 2}),
    "maxpool2d_overlapping": lambda: (
        "maxpool2d", (_full((1, 3, 11, 11), 18),), {"window": 3, "stride": 2}),
    "avgpool2d_150_windows": lambda: (
        "avgpool2d", (_full((2, 3, 10, 10), 19, -(1 << 28), 1 << 28),), {"window": 2}),
    "global_avgpool_15_rows": lambda: (
        "global_avgpool", (_full((3, 5, 4, 4), 20, -(1 << 27), 1 << 27),), {}),
    # the channels-last paths (``tiling.nhwc_rows``): C = 64 and C = 3, int8
    # and int32, wrap-prone values; row blocks that do not divide N·H
    "relu_channels_last_c64_near_wrap": lambda: (
        "relu", (_near_wrap((2, 64, 9, 9), 47),), {}),
    "relu_channels_last_c64_int8": lambda: (
        "relu", (_full((2, 64, 9, 9), 48, -128, 128).astype(jnp.int8),), {}),
    "relu_channels_last_c3_near_wrap": lambda: (
        "relu", (_near_wrap((3, 3, 7, 9), 49),), {}),
    "relu_channels_last_c3_int8": lambda: (
        "relu", (_full((3, 3, 7, 9), 50, -128, 128).astype(jnp.int8),), {}),
    # 51·9 = 459 rows of 576 lanes: a 448-row block and a last one past N·H
    "relu_channels_last_partial_row_block": lambda: (
        "relu", (_near_wrap((51, 64, 9, 9), 51),), {}),
    "ewise_add_channels_last_c64_near_wrap": lambda: (
        "ewise_add", (_near_wrap((2, 64, 9, 9), 52), _near_wrap((2, 64, 9, 9), 53)), {}),
    "ewise_add_channels_last_c64_int8_wraps": lambda: (
        "ewise_add", (_full((2, 64, 9, 9), 54, -128, 128).astype(jnp.int8),
                      _full((2, 64, 9, 9), 55, -128, 128).astype(jnp.int8)), {}),
    "ewise_add_channels_last_c3_near_wrap": lambda: (
        "ewise_add", (_near_wrap((3, 3, 7, 9), 56), _near_wrap((3, 3, 7, 9), 57)), {}),
    "ewise_add_channels_last_c3_int8_wraps": lambda: (
        "ewise_add", (_full((3, 3, 7, 9), 58, -128, 128).astype(jnp.int8),
                      _full((3, 3, 7, 9), 59, -128, 128).astype(jnp.int8)), {}),
    "maxpool2d_w2_odd_near_wrap": lambda: (
        "maxpool2d", (_near_wrap((2, 5, 9, 11), 60),), {"window": 2}),
    "maxpool2d_w3_s2_odd_c64": lambda: (
        "maxpool2d", (_full((2, 64, 9, 11), 61),), {"window": 3, "stride": 2}),
    "maxpool2d_w2_int8": lambda: (
        "maxpool2d", (_full((2, 3, 9, 9), 62, -128, 128).astype(jnp.int8),), {"window": 2}),
    # 10 images of 10 rows: 8 a block, the last block runs past N
    "maxpool2d_images_past_n": lambda: (
        "maxpool2d", (_near_wrap((10, 3, 10, 10), 63),), {"window": 2}),
    "avgpool2d_w2_odd": lambda: (
        "avgpool2d", (_full((2, 5, 9, 11), 64, -(1 << 28), 1 << 28),), {"window": 2}),
    "avgpool2d_w3_odd_negative": lambda: (
        "avgpool2d", (_full((2, 3, 11, 10), 65, -(1 << 20), 10),), {"window": 3}),
    "global_avgpool_negative_sums": lambda: (
        "global_avgpool", (_full((3, 5, 7, 7), 66, -(1 << 20), 10),), {}),
    "global_avgpool_images_past_n": lambda: (
        "global_avgpool", (_full((10, 512, 7, 7), 67, -(1 << 22), 1 << 22),), {}),
    "softmax_37_rows": lambda: (
        "softmax_fixedpoint", (_full((37, 19), 21, -3000, 3000),), {"in_frac": 7}),
    "kv_append_13_rows": lambda: (
        "kv_append", (_full((13, 20), 22), _full((20,), 23),
                      jnp.zeros(13, jnp.int32).at[12].set(1)), {}),
    "htree_reduce_d100_bf16": lambda: (
        "htree_reduce",
        (jax.random.normal(jax.random.key(24), (16, 100)).astype(jnp.bfloat16),), {}),
}


@pytest.mark.parametrize("case", sorted(_AWKWARD))
def test_kernel_awkward_shape_bit_exact(case):
    name, args, kwargs = _AWKWARD[case]()
    with use_backend("xla"):
        want = api.dispatch(name, *args, **kwargs)
    with use_backend("interpret"):
        got = api.dispatch(name, *args, **kwargs)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


@pytest.mark.parametrize("n", [1, 2, 3, 4])
def test_conv_digits_equal_int_slices(n):
    """The conv wrapper's one-pass digit split gives ``int_slices``' digits
    for every int32 value, in range of the hint or not."""
    from repro.kernels.bitslice_matmul import int_slices
    from repro.kernels.conv import _digits

    edge = np.array([_I32[0], _I32[1], 0, -1, 1, 127, 128, -128, -129, 32767, 32768, -32768,
                     -32769, (1 << 23) - 1, 1 << 23, -(1 << 23), -(1 << 23) - 1], np.int64)
    rnd = np.random.default_rng(n).integers(_I32[0], _I32[1], 5000)
    x = jnp.asarray(np.concatenate([edge, rnd]).astype(np.int32))
    np.testing.assert_array_equal(np.stack(_digits(x, n)), np.asarray(int_slices(x, n)))


@pytest.mark.parametrize("wq,want", [(58, 64), (30, 32), (29, 32), (28, 32), (16, 16),
                                     (15, 16), (8, 8), (7, 8), (9, 9), (4, 4), (3, 3)])
def test_conv_grid_rows_fill_whole_tiles(wq, want):
    """The conv's row width is rounded up to a whole 8-row tile unless that
    adds more than a quarter of the rows."""
    from repro.kernels.conv import _tile_rows

    assert _tile_rows(wq) == want


def _paths(fn, *args):
    """The kernel paths (``tiling.path_counts``) tracing ``fn`` takes."""
    from repro.kernels import tiling

    tiling.reset_path_counts()
    with use_backend("interpret"):
        jax.eval_shape(fn, *args)
    return tiling.path_counts()


@pytest.mark.parametrize("c,k,path", [(3, 3, "patches"), (32, 3, "patches"), (64, 3, "taps"),
                                      (3, 1, "taps"), (512, 3, "taps")])
def test_conv2d_path_follows_channels(c, k, path):
    x = jax.ShapeDtypeStruct((1, c, 8, 8), jnp.int32)
    w = jax.ShapeDtypeStruct((4, c, k, k), jnp.int32)
    assert _paths(lambda x, w: api.conv2d(x, w, padding=k // 2), x, w) == {f"conv2d/{path}": 1}


@pytest.mark.parametrize("shape,path", [((2, 64, 9, 9), "channels_last"),
                                        ((2, 3, 7, 9), "channels_last"),
                                        ((37, 19), "lane_rows"), ((4, 7, 9), "lane_rows"),
                                        ((945,), "lane_rows")])
@pytest.mark.parametrize("kernel", ["relu", "ewise_add"])
def test_elementwise_path_follows_rank(kernel, shape, path):
    """A 4-D operand is mapped over its channels-last rows; any other rank
    over rows of 128 lanes."""
    x = jax.ShapeDtypeStruct(shape, jnp.int32)
    fn = api.relu if kernel == "relu" else (lambda x: api.ewise_add(x, x))
    assert _paths(fn, x) == {f"{kernel}/{path}": 1}


def test_conv2d_lowers_without_gather_or_patch_matrix():
    """A ResNet-18 stage-1 conv at batch 64, lowered for the TPU (nothing
    runs): no gather, and no int32 value over twice the activation (the
    im2col matrix was nine times it)."""
    import math
    import re

    x = jax.ShapeDtypeStruct((64, 64, 56, 56), jnp.int32)
    w = jax.ShapeDtypeStruct((64, 64, 3, 3), jnp.int32)
    with use_backend("pallas"):
        text = jax.jit(
            lambda x, w: api.conv2d(x, w, stride=1, padding=1, x_bits=21, w_bits=8)
        ).trace(x, w).lower(lowering_platforms=("tpu",)).as_text()
    assert "gather" not in text
    assert text.count("tpu_custom_call") == 1
    int32_sizes = [math.prod(int(d) for d in m.split("x"))
                   for m in re.findall(r"tensor<([0-9x]+)xi32>", text)]
    assert max(int32_sizes) <= 2 * math.prod(x.shape)


def test_resnet18_lowers_19_taps_1_patches_no_gather():
    """The ResNet-18 Program lowered for the TPU (nothing runs): the stem
    takes the patch slab, the 19 other convs the taps, and no conv gathers."""
    from repro.kernels import tiling
    from repro.models import resnet

    cfg = resnet.RESNET18
    params, x = jax.eval_shape(lambda: (resnet.init_params(cfg), resnet.make_input(cfg, batch=2)))
    traced = api.trace(lambda p, x: resnet.forward(cfg, p, x), name="resnet18")
    tiling.reset_path_counts()
    with use_backend("pallas"):
        ex = api.compile(traced.trace(params, x))
        text = jax.jit(lambda p, x: ex(p, x)).trace(params, x).lower(
            lowering_platforms=("tpu",)).as_text()
    counts = tiling.path_counts()
    assert {k: v for k, v in counts.items() if k.startswith("conv2d/")} == {
        "conv2d/taps": 19, "conv2d/patches": 1}
    assert "gather" not in text


def test_resnet18_activations_stay_channels_last():
    """Every relu, residual add and pool of the benchmark's ResNet-18 forward
    (112x112 images, a 2x2 stem max pool) takes the channels-last path: 17
    relu, 8 add, 1 max pool and 1 global pool, so the transposes between
    kernels cancel."""
    import json

    from chipbench.harness import BENCH, load_module
    from repro.models import resnet

    rn = load_module(BENCH / "configs" / "resnet18.py")
    cfg = rn.program_config(json.loads((BENCH / "configs" / "resnet18.json").read_text()))
    params, x = jax.eval_shape(lambda: (resnet.init_params(cfg), resnet.make_input(cfg, batch=2)))
    traced = api.trace(lambda p, x: resnet.forward(cfg, p, x), name="resnet18")
    counts = _paths(lambda p, x: api.compile(traced.trace(params, x))(p, x), params, x)
    assert counts == {"conv2d/taps": 19, "conv2d/patches": 1, "relu/channels_last": 17,
                      "ewise_add/channels_last": 8, "maxpool2d/channels_last": 1,
                      "global_avgpool/channels_last": 1}


def test_rglru_scan_padded_blocks():
    """T and W that are no multiples of the (8, 128) tile."""
    ks = jax.random.split(jax.random.key(0), 3)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (2, 37, 200)))
    b = jax.random.normal(ks[1], (2, 37, 200))
    h0 = jax.random.normal(ks[2], (2, 200))
    with use_backend("interpret"):
        got = api.rglru_scan(a, b, h0, block_t=16, block_w=128)
    np.testing.assert_allclose(np.asarray(ref.rglru_scan_ref(a, b, h0)), np.asarray(got),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("bits,n", [(None, 4), (1, 1), (8, 1), (9, 2), (15, 2),
                                    (16, 3), (24, 4), (32, 4)])
def test_int_slices_are_exact_within_hint(bits, n):
    from repro.kernels.bitslice_matmul import int_slices, slices_for_bits

    assert slices_for_bits(bits) == n
    b = 32 if bits is None else bits
    lo, hi = -(1 << (b - 1)), (1 << (b - 1)) - 1
    edge = np.array([lo, hi, 0, -1, 1, lo + 1, hi - 1], np.int64)
    rnd = np.random.default_rng(b).integers(lo, hi + 1, 200)
    x = jnp.asarray(np.concatenate([edge, rnd]).astype(np.int32))
    s = int_slices(x, n)
    assert s.dtype == jnp.int8 and s.shape == (n, x.size)
    recon = sum(np.asarray(s[i], np.int64) << (8 * i) for i in range(n))
    if n < 4:
        np.testing.assert_array_equal(recon, np.asarray(x, np.int64))
    else:  # all four slices: exact modulo 2^32
        np.testing.assert_array_equal(recon % (1 << 32), np.asarray(x, np.int64) % (1 << 32))
