"""Analytic FLOP accounting for the U-Net, for MFU reporting.

MFU = achieved FLOP/s over the chip's peak. The count mirrors the exact
layer ladder of ``models/unet.UNet`` (reference architecture:
pkg/segmentation_model.py:86-120): every 3x3/1x1 conv at 2*K^2*H*W*Cin*Cout
FLOPs plus the two interpolation matmuls of each bilinear upsample. Pooling,
normalization, activations, and the geometry pipeline are omitted -- they
are O(elements), under 1% of the conv total at the deployed shapes (the
convention used by the standard MFU literature, which counts matmul FLOPs
only). The count is validated against XLA's own ``cost_analysis`` in
tests/test_pallas.py.

Peaks live in ONE table, :data:`CHIP_PEAKS`, keyed by the ``device_kind``
JAX reports. Every utilization or roofline figure names the device it is
taken against: callers look the device up with :func:`chip_peaks`, and a
device that is not in the table is an error, never a default.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks a roofline is drawn against."""

    bf16_tflops: float  # dense bf16 matmul peak, TFLOP/s
    hbm_gbps: float  # HBM bandwidth, GB/s


#: ``jax.devices()[0].device_kind`` -> peaks. Source: Google Cloud
#: documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM per chip).
CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(bf16_tflops=197.0, hbm_gbps=819.0),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks for one ``device_kind``; an unknown device is an error."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r} "
            f"(known: {sorted(CHIP_PEAKS)}); add it to "
            "utils/flops.CHIP_PEAKS with its source before reporting "
            "utilization against it"
        ) from None


def roofline_ms(flops: int, bytes_moved: int, peaks: ChipPeaks) -> dict:
    """Roofline lower bound for one kernel launch: compute time at the
    chip's dense peak vs memory time for the given minimal HBM traffic.
    A launch cannot run faster than ``max(compute_ms, memory_ms)``; real
    traffic (halos, re-reads) is strictly larger than the minimum the
    callers count, so the bound is optimistic and 'percent of bound' is a
    conservative utilization figure."""
    compute_ms = flops / (peaks.bf16_tflops * 1e12) * 1e3
    memory_ms = bytes_moved / (peaks.hbm_gbps * 1e9) * 1e3
    return {
        "flops": flops,
        "bytes": bytes_moved,
        "compute_ms": compute_ms,
        "memory_ms": memory_ms,
        "bound_ms": max(compute_ms, memory_ms),
        "bound_by": "compute" if compute_ms >= memory_ms else "memory",
    }


def conv3x3_roofline_ms(h: int, w: int, cin: int, cout: int,
                        batch: int = 1, itemsize: int = 2, *,
                        peaks: ChipPeaks) -> dict:
    """Roofline for one fused 3x3 conv+BN+ReLU launch: minimal traffic is
    read input once, read weights once, write output once."""
    return roofline_ms(
        2 * 9 * batch * h * w * cin * cout,
        itemsize * (
            batch * h * w * cin + 9 * cin * cout + batch * h * w * cout
        ),
        peaks,
    )


def conv1x1_roofline_ms(h: int, w: int, cin: int, cout: int,
                        batch: int = 1, itemsize: int = 2, *,
                        peaks: ChipPeaks) -> dict:
    """Roofline for the fused 1x1 head launch."""
    return roofline_ms(
        2 * batch * h * w * cin * cout,
        itemsize * (
            batch * h * w * cin + cin * cout + batch * h * w * cout
        ),
        peaks,
    )


def conv_transpose2x2_roofline_ms(h: int, w: int, cin: int, cout: int,
                                  batch: int = 1,
                                  itemsize: int = 2, *,
                                  peaks: ChipPeaks) -> dict:
    """Roofline for the 2x2 stride-2 transposed-conv launch (each INPUT
    pixel spawns four taps; output is [2H, 2W])."""
    return roofline_ms(
        2 * 4 * batch * h * w * cin * cout,
        itemsize * (
            batch * h * w * cin + 4 * cin * cout
            + batch * 4 * h * w * cout
        ),
        peaks,
    )


def deproject_roofline_ms(h: int, w: int, *, peaks: ChipPeaks) -> dict:
    """Roofline for the fused deproject+edge-stats kernel
    (ops/pallas/geometry.py): ~12 VPU ops per pixel (two iota builds, the
    z/x/y formulas, the validity test, five masked reductions) against
    reading mask+depth once (f32) and writing the four maps once.
    Bandwidth-bound by construction -- the kernel's whole purpose is
    collapsing the XLA chain's multiple HBM passes into one."""
    return roofline_ms(12 * h * w, 4 * (2 * h * w + 4 * h * w), peaks)


def bspline_design_roofline_ms(n: int, c: int, d: int = 3,
                               degree: int = 3, *, peaks: ChipPeaks) -> dict:
    """Roofline for the fused B-spline design kernel: the Cox-de Boor
    recursion (~8 VPU ops per (point, basis-function) per level) plus the
    two MXU contractions, against reading u/w/points once and writing the
    [C, C]+[C, D] outputs -- the [N, C] basis matrix itself never touches
    HBM (that is the fusion's point, and why the XLA chain's traffic is
    ~(2 + degree) x larger)."""
    basis_flops = 8 * degree * n * (c + degree)
    mm_flops = 2 * n * c * c + 2 * n * c * d
    return roofline_ms(
        basis_flops + mm_flops,
        4 * (n * (2 + d) + c * c + c * d),
        peaks,
    )


def bspline_curvature_roofline_ms(n: int, c: int, d: int = 3,
                                  degree: int = 3, *,
                                  peaks: ChipPeaks) -> dict:
    """Roofline for the fused curvature kernel: three basis builds, three
    design+evaluate matmul chains, and the cross/norm formula (~40 VPU
    ops per sample), against ctrl+u in / kappa+valid+r out."""
    basis_flops = 3 * 8 * degree * n * (c + degree)
    mm_flops = 2 * n * c * d * 3 + 2 * n * (c + degree) * c * 2
    return roofline_ms(
        basis_flops + mm_flops + 40 * n,
        4 * (c * d + n + n * (2 + d)),
        peaks,
    )


def jpeg_dequant_roofline_ms(n_blocks: int, batch: int = 1, *,
                             peaks: ChipPeaks) -> dict:
    """Roofline for the standalone dequantize stage (one int multiply per
    coefficient against the broadcast [64] quant row): read int16
    coefficients, write int32 products. Counted separately only for the
    analytic table -- the shipped kernel fuses it into the IDCT matmuls,
    which is why the fused bound below charges the int16 read once."""
    n = batch * n_blocks * 64
    return roofline_ms(n, 2 * n + 4 * n, peaks)


def jpeg_idct_roofline_ms(n_blocks: int, batch: int = 1, *,
                          peaks: ChipPeaks) -> dict:
    """Roofline for the fused dequant+IDCT launch
    (ops/pallas/decode.dequant_idct): two [N, 64] x [64, 64] integer basis
    matmuls per pass over the block axis (islow's two passes), plus the
    dequant multiply and the descale/clamp elementwise tail, against
    reading the int16 coefficients + [64] quant row once and writing the
    int32 samples once. At 64 blocks of reuse per basis element the
    arithmetic intensity is ~43 FLOP/byte of coefficient traffic, yet the
    tiny 64-wide contractions leave the MXU idle enough that the launch
    stays bandwidth-bound at every deployed shape -- which is the point:
    the decode stage must ride free under the analyzer's compute."""
    n = batch * n_blocks
    matmul_flops = 2 * (2 * n * 64 * 64)
    elementwise_flops = 3 * n * 64  # dequant mul + two descale add/shifts
    return roofline_ms(
        matmul_flops + elementwise_flops,
        2 * n * 64 + 2 * 64 + 4 * n * 64,
        peaks,
    )


def chroma_upsample_roofline_ms(h: int, w: int, batch: int = 1,
                                subsampling: str = "420", *,
                                peaks: ChipPeaks) -> dict:
    """Roofline for the fancy (triangle) chroma upsample of both chroma
    planes to the [H, W] luma grid: ~6 integer VPU ops per output sample
    (two neighbor adds, two scaled sums, bias, shift) per plane, against
    reading the subsampled planes and writing the full-resolution ones."""
    if subsampling == "444":
        return roofline_ms(0, 0, peaks)
    div = 4 if subsampling == "420" else 2
    in_px = 2 * batch * h * w // div
    out_px = 2 * batch * h * w
    return roofline_ms(6 * out_px, 4 * (in_px + out_px), peaks)


def ycbcr_to_rgb_roofline_ms(h: int, w: int, batch: int = 1, *,
                             peaks: ChipPeaks) -> dict:
    """Roofline for the fixed-point YCbCr->RGB convert + clamp: ~12
    integer VPU ops per pixel against reading three int32 planes and
    writing the uint8 RGB image."""
    px = batch * h * w
    return roofline_ms(12 * px, 4 * 3 * px + 3 * px, peaks)


def jpeg_decode_roofline_ms(h: int, w: int, batch: int = 1,
                            subsampling: str = "420", *,
                            peaks: ChipPeaks) -> dict:
    """Combined roofline for the whole on-chip decode stage
    (ops/pipeline.decode_coef_batch): dequant+IDCT over every block of all
    three components, chroma upsample, color convert. The gate
    bench_pallas.py applies: this stage must be bandwidth-bound (bound_by
    == "memory") -- decode rides the analyzer's HBM streams, it does not
    compete for its MXU."""
    sh, sv = {"444": (1, 1), "420": (2, 2), "422": (2, 1)}[subsampling]
    mcux = -(-w // (8 * sh))
    mcuy = -(-h // (8 * sv))
    blocks_y = (mcuy * sv) * (mcux * sh)
    blocks_c = 2 * mcuy * mcux
    idct = jpeg_idct_roofline_ms(blocks_y + blocks_c, batch, peaks=peaks)
    ups = chroma_upsample_roofline_ms(h, w, batch, subsampling,
                                      peaks=peaks)
    ycc = ycbcr_to_rgb_roofline_ms(h, w, batch, peaks=peaks)
    return roofline_ms(
        idct["flops"] + ups["flops"] + ycc["flops"],
        idct["bytes"] + ups["bytes"] + ycc["bytes"],
        peaks,
    )


def mask_bitpack_roofline_ms(h: int, w: int, batch: int = 1, *,
                             peaks: ChipPeaks) -> dict:
    """Roofline for the egress mask bitpack (ops/pallas/pack.bitpack_mask):
    ~2 integer VPU ops per input pixel (the nonzero test and one
    shift-accumulate step of the unrolled 8-way reduction), against
    reading the [B, H, W] uint8 mask once and writing the 8x-smaller
    [B, H, ceil(W/8)] packed bytes once. At ~2 FLOP per ~1.1 bytes the
    launch is bandwidth-bound by construction -- one HBM pass over the
    mask, which is the point: packing must ride free under the analyzer,
    and the D2H payload it buys shrinks 8x (bench_pallas.py asserts the
    bound class)."""
    px = batch * h * w
    return roofline_ms(2 * px, px + batch * h * ((w + 7) // 8), peaks)


def unet_forward_flops(img_size: int = 256, base: int = 64,
                       in_ch: int = 3, num_classes: int = 1,
                       bilinear: bool = True) -> int:
    """FLOPs of one forward pass at batch 1 (multiply-adds counted as 2)."""
    f = base
    factor = 2 if bilinear else 1

    def dconv(h: int, cin: int, mid: int, cout: int) -> int:
        return 2 * 9 * h * h * (cin * mid + mid * cout)

    total = 0
    # encoder: inc + 4 downs; spatial halves each level
    enc = [f, 2 * f, 4 * f, 8 * f, 16 * f // factor]
    h = img_size
    total += dconv(h, in_ch, f, f)
    prev = f
    for c in enc[1:]:
        h //= 2
        total += dconv(h, prev, c, c)
        prev = c
    # decoder: 4 ups; each doubles spatial, interpolation matmuls + DoubleConv
    skips = [8 * f, 4 * f, 2 * f, f]
    feats = [8 * f // factor, 4 * f // factor, 2 * f // factor, f]
    x_ch = enc[-1]
    for skip, feat in zip(skips, feats):
        h2 = h * 2
        if bilinear:
            # upsample_align_corners: the H pass [h2,h]x[h,(w c)], then
            # the W pass [w2,w]x[w,c] for each of the h2 rows, with
            # w == h, w2 == h2; the same count in either form it writes
            # the two products in
            total += 2 * h2 * h * h * x_ch + 2 * h2 * h2 * h * x_ch
        else:
            # 2x2 stride-2 transpose conv: each INPUT pixel spawns four
            # taps, so the cost scales with the input's h*h
            total += 2 * 4 * h * h * x_ch * (x_ch // 2)
        cat = x_ch + skip if bilinear else x_ch // 2 + skip
        # bilinear Up: mid_features = (x + skip concat) // 2 (models/unet.Up)
        mid = cat // 2 if bilinear else feat
        total += dconv(h2, cat, mid, feat)
        x_ch = feat
        h = h2
    # 1x1 head
    total += 2 * img_size * img_size * x_ch * num_classes
    return total


def mfu(flops: int, seconds: float, peaks: ChipPeaks) -> float:
    """Fraction of the chip's bf16 peak: (flops / seconds) / peak."""
    return (flops / max(seconds, 1e-12)) / (peaks.bf16_tflops * 1e12)
