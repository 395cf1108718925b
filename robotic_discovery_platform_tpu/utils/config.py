"""Dataclass configuration layer.

The reference has no config system at all -- every behavior is a module-level
constant (reference: scripts/train_segmenter.py:45-63, services/vision_analysis/
server.py:50-65, services/vision_analysis/client.py:43-45, pkg/camera.py:35,
scripts/monitoring/drift_detector.py:21-22, scripts/01_calibrate_camera.py:37-38,
scripts/02_collect_segmentation_data.py:40-42). This module replaces that with
frozen dataclasses whose *defaults are exactly the reference constants*, plus
``from_flags`` CLI overrides, so every entry point is configurable without
editing source.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Sequence


@dataclass(frozen=True)
class CameraConfig:
    """Reference: pkg/camera.py:35 (640x480 @ 30 FPS, depth z16 + color bgr8)."""

    width: int = 640
    height: int = 480
    fps: int = 30


@dataclass(frozen=True)
class ModelConfig:
    """Reference architecture constants: pkg/segmentation_model.py:86-120.

    Channel ladder 64 -> 128 -> 256 -> 512 -> 1024//factor, ``factor == 2``
    when ``bilinear`` (the deployed default -- the reference instantiates
    ``UNet(3, 1)`` everywhere: scripts/train_segmenter.py:143).
    """

    in_channels: int = 3
    num_classes: int = 1
    bilinear: bool = True
    base_features: int = 64
    # TPU-first knobs (no reference equivalent):
    compute_dtype: str = "bfloat16"  # MXU-native; params stay float32
    norm: str = "batch"  # "batch" matches reference; "group" is jit-friendlier
    # Weight-init family: "torch" reproduces torch Conv2d's default
    # kaiming_uniform_(a=sqrt(5)) so seed-for-seed comparisons against the
    # reference anchor are init-fair (models/unet._kernel_init); "lecun" is
    # the Flax default family.
    init: str = "torch"
    # Training-path conv implementation for the DoubleConv 3x3 convs.
    # "auto" (default): ops/pallas/conv.conv3x3 picks by the layer's shape.
    # On a TPU a batch of at most 4 with at most 2^18 pixels (batch x H x
    # W) runs the custom-VJP Pallas forward, dx and dw kernels, every
    # other shape the plain XLA convolution with JAX's own derivative --
    # the program "flax" compiles. Measured on one TPU v5e under jax 0.9.0
    # (PR 34, device ms a step in the scan epoch at 256^2, all-Pallas vs
    # plain): 22.43 vs 23.56 at the reference batch 4, 48.96 vs 28.96 at
    # batch 8, 207.62 vs 115.72 at batch 32. "flax" = nn.Conv end to end
    # -- the trainer forces this under a device mesh, where the custom
    # kernels have no pjit partitioning rules. "pallas"/"xla"/"interpret"
    # pin the custom-VJP dispatch for tests.
    conv_impl: str = "auto"


@dataclass(frozen=True)
class BlockDiffLMConfig:
    """A sparse-expert decoder trained by block diffusion, as one chip's
    share of an expert-parallel job (models/blockdiff_lm.py). Widths are a
    published model's; ``num_layers``, ``experts_held`` and ``vocab_size``
    are what this chip holds of it. The defaults are the unit tests' size.
    """

    vocab_size: int = 64          # rows of the embedding and head held here
    hidden_size: int = 64
    num_layers: int = 2
    num_heads: int = 4
    num_kv_heads: int = 2         # each shared by num_heads // num_kv_heads
    head_dim: int = 16
    num_experts: int = 8          # the router's outputs, all chips' experts
    experts_per_token: int = 2
    experts_held: int = 2         # ids first_expert .. first_expert + held - 1
    first_expert: int = 0
    expert_width: int = 32
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    norm_topk_prob: bool = True
    # the expert layer's form (models/moe.py), the same five fields on
    # every sparse-expert configuration: how the router scores ("softmax",
    # or "sigmoid": scores picked with a bias that takes no gradient,
    # renormalised, times routed_scaling_factor), what an expert computes
    # ("swiglu": Wdown(silu(x Wgate) * (x Wup)); "relu2": Wdown
    # relu(x Wup)^2) and the width of a shared expert every token passes
    # through (0 = none); router_norm_eps is what a sigmoid router's picked
    # scores are divided by beside their sum
    router_scoring: str = "softmax"
    routed_scaling_factor: float = 1.0
    router_norm_eps: float = 1e-20
    expert_act: str = "swiglu"
    shared_expert_width: int = 0
    seq_len: int = 32             # L tokens; the model sees 2 L positions
    block_length: int = 4
    mask_token_id: int = 63       # inside the slice; no data token takes it
    # sorted rows an expert layer gathers, multiplies and scatters at a
    # time; chunks past the last routed row are skipped (a multiple of the
    # grouped product's row tile; it divides positions x experts_per_token)
    moe_chunk_rows: int = 64
    compute_dtype: str = "bfloat16"  # params stay float32
    # normal(0, init_std) matrices, the embedding at embed_init_std. Seeded
    # weights that stand in for trained ones set it well above init_std:
    # attention passes on what a sequence's positions share and averages a
    # token's own part away, so at equal scales the layers' hidden states
    # end on one direction and every position picks the same experts
    init_std: float = 0.02
    embed_init_std: float = 0.02
    # "auto": the Pallas kernels (attention under the block-diffusion mask,
    # the experts' grouped product) on a TPU, dense jax.numpy elsewhere;
    # "pallas" / "interpret" / "xla" pin one for tests.
    kernel_impl: str = "auto"

    def __post_init__(self):
        _check_expert_form(self)


@dataclass(frozen=True)
class RotaryConfig:
    """One rotary table (rotate-half form over the whole head):
    ``inv_freq_i = theta^(-2i/d)``. With ``factor`` above 1 the table is
    YaRN's (models/causal_lm.rope_table): the slow frequencies divided by
    ``factor`` over a ramp set by ``original_max_position``, ``beta_fast``
    and ``beta_slow``, cos and sin multiplied by ``attention_factor``."""

    theta: float = 1e6
    factor: float = 1.0
    original_max_position: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


#: the attention kinds a ``CausalLMConfig.layer_types`` entry may name
ATTENTION_KINDS = ("sliding_attention", "full_attention")


@dataclass(frozen=True)
class CausalLMConfig:
    """A causal sparse-expert decoder whose layers differ in kind, as one
    chip's share of an expert-parallel job (models/causal_lm.py):
    ``layer_types`` names each layer's attention, under the full causal mask
    or inside a window of ``sliding_window`` keys, and each kind has a
    rotary table of its own. Widths are a published model's;
    ``num_layers``, ``experts_held`` and ``vocab_size`` are what this chip
    holds of it. The defaults are the unit tests' size: two periods of a
    two-layer pattern, the window shorter than the sequence."""

    vocab_size: int = 64          # rows of the embedding and head held here
    hidden_size: int = 64
    num_layers: int = 4
    num_heads: int = 4
    num_kv_heads: int = 2         # each shared by num_heads // num_kv_heads
    head_dim: int = 16
    num_experts: int = 8          # the router's outputs, all chips' experts
    experts_per_token: int = 2
    experts_held: int = 2         # ids first_expert .. first_expert + held - 1
    first_expert: int = 0
    expert_width: int = 32
    rms_norm_eps: float = 1e-6
    norm_topk_prob: bool = True
    # the expert layer's form (models/moe.py), the same five fields on
    # every sparse-expert configuration: how the router scores ("softmax",
    # or "sigmoid": scores picked with a bias that takes no gradient,
    # renormalised, times routed_scaling_factor), what an expert computes
    # ("swiglu": Wdown(silu(x Wgate) * (x Wup)); "relu2": Wdown
    # relu(x Wup)^2) and the width of a shared expert every token passes
    # through (0 = none); router_norm_eps is what a sigmoid router's picked
    # scores are divided by beside their sum
    router_scoring: str = "softmax"
    routed_scaling_factor: float = 1.0
    router_norm_eps: float = 1e-20
    expert_act: str = "swiglu"
    shared_expert_width: int = 0
    seq_len: int = 32             # L tokens a sequence
    # one entry a layer; the model runs the shortest period of the pattern
    # layer by layer and repeats it
    layer_types: tuple = ("sliding_attention", "full_attention") * 2
    sliding_window: int = 8       # a query sees itself and the window - 1 before
    sliding_rope: RotaryConfig = RotaryConfig(theta=100.0)
    # at head_dim 16 the ramp runs over frequencies 1 .. 7 of the 8
    full_rope: RotaryConfig = RotaryConfig(
        theta=100.0, factor=4.0, original_max_position=64, beta_fast=4.0,
        beta_slow=0.25, attention_factor=1.1386294361119891)
    # as BlockDiffLMConfig's
    moe_chunk_rows: int = 64
    compute_dtype: str = "bfloat16"  # params stay float32
    init_std: float = 0.02
    embed_init_std: float = 0.02
    kernel_impl: str = "auto"

    def __post_init__(self):
        # from a JSON document the pattern is a list and the tables dicts
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        for name in ("sliding_rope", "full_rope"):
            if isinstance(getattr(self, name), dict):
                object.__setattr__(
                    self, name, RotaryConfig(**getattr(self, name)))
        unknown = set(self.layer_types) - set(ATTENTION_KINDS)
        if unknown or len(self.layer_types) != self.num_layers:
            raise ValueError(
                f"layer_types names {self.num_layers} layers, each one of "
                f"{ATTENTION_KINDS}; got {self.layer_types}")
        _check_expert_form(self)


#: the layer kinds a ``HybridLMConfig.layer_pattern`` entry may name, and
#: the letters a published ``hybrid_override_pattern`` writes them with
HYBRID_KINDS = {"M": "mamba", "*": "attention", "E": "experts",
                "c": "shortconv", "m": "mlp"}
ROUTER_SCORINGS = ("softmax", "sigmoid")
EXPERT_ACTS = ("swiglu", "relu2")


@dataclass(frozen=True)
class HybridLMConfig:
    """A causal decoder whose layers are one residual branch each, a
    Mamba-2 mixer, a gated short convolution, grouped-query attention, a
    dense gated MLP or a sparse-expert layer, as one chip's share of an
    expert-parallel job (models/hybrid_lm.py). ``layer_pattern`` names each
    layer's kind (a tuple of ``HYBRID_KINDS`` values, or a string of their
    letters as a published ``hybrid_override_pattern`` has them); a model
    whose published layer is an operator and a feed-forward branch is two
    entries a layer. Widths are a published model's; ``num_layers``,
    ``experts_held`` and ``vocab_size`` are what this chip holds of it. The
    defaults are the unit tests' size: two periods of a four-layer pattern,
    four chunks a sequence, two groups."""

    vocab_size: int = 64          # rows of the embedding and head held here
    hidden_size: int = 64
    num_layers: int = 8
    layer_pattern: tuple = "ME*E" * 2
    rms_norm_eps: float = 1e-5
    seq_len: int = 32             # L tokens a sequence
    # the mixer: mamba_heads x mamba_head_dim inner channels; B and C are
    # shared by the mamba_heads // ssm_groups heads of a group
    mamba_heads: int = 4
    mamba_head_dim: int = 16
    ssm_groups: int = 2
    ssm_state: int = 16
    conv_kernel: int = 4
    ssm_chunk: int = 8            # positions a chunk of the scan
    # where the seeded start draws the mixer's step dt = softplus(dt_bias)
    # (log-uniform on [min, max], floored) and A = -a, a uniform on a_range
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    a_range: tuple = (1.0, 16.0)
    # the gated short convolution: shortconv_kernel taps over hidden_size
    # channels, no bias
    shortconv_kernel: int = 3
    # attention; rope_theta 0 = no rotary embedding, qk_norm = an RMSNorm
    # over each head of q and k before it
    num_heads: int = 4
    num_kv_heads: int = 2         # each shared by num_heads // num_kv_heads
    head_dim: int = 16
    qk_norm: bool = False
    rope_theta: float = 0.0
    mlp_width: int = 128          # the dense MLP: hidden -> width -> hidden
    # the embedding's rows are the head's columns: one leaf, no ``head``
    tie_embeddings: bool = False
    # the expert layer (models/moe.py)
    num_experts: int = 8          # the router's outputs, all chips' experts
    experts_per_token: int = 2
    experts_held: int = 2         # ids first_expert .. first_expert + held - 1
    first_expert: int = 0
    expert_width: int = 32
    norm_topk_prob: bool = True
    router_scoring: str = "sigmoid"
    routed_scaling_factor: float = 2.5
    # what a sigmoid router's picked scores are divided by beside their sum
    router_norm_eps: float = 1e-20
    expert_act: str = "relu2"
    shared_expert_width: int = 64
    # as BlockDiffLMConfig's
    moe_chunk_rows: int = 64
    compute_dtype: str = "bfloat16"  # params stay float32
    init_std: float = 0.02
    embed_init_std: float = 0.02
    kernel_impl: str = "auto"

    def __post_init__(self):
        pattern = self.layer_pattern
        if isinstance(pattern, str):
            unknown = set(pattern) - set(HYBRID_KINDS)
            if unknown:
                raise ValueError(f"layer_pattern letters {sorted(unknown)} "
                                 f"are none of {sorted(HYBRID_KINDS)}")
            pattern = [HYBRID_KINDS[letter] for letter in pattern]
        object.__setattr__(self, "layer_pattern", tuple(pattern))
        object.__setattr__(self, "a_range", tuple(self.a_range))
        unknown = set(self.layer_pattern) - set(HYBRID_KINDS.values())
        if unknown or len(self.layer_pattern) != self.num_layers:
            raise ValueError(
                f"layer_pattern names {self.num_layers} layers, each one of "
                f"{tuple(HYBRID_KINDS.values())}; got {self.layer_pattern}")
        if self.mamba_heads % self.ssm_groups:
            raise ValueError(f"{self.mamba_heads} mixer heads in "
                             f"{self.ssm_groups} groups")
        _check_expert_form(self)

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """The channels the convolution runs over: xs, B and C."""
        return self.mamba_inner + 2 * self.ssm_groups * self.ssm_state


def _check_expert_form(cfg) -> None:
    if (cfg.router_scoring not in ROUTER_SCORINGS
            or cfg.expert_act not in EXPERT_ACTS):
        raise ValueError(
            f"router_scoring is one of {ROUTER_SCORINGS} and expert_act one "
            f"of {EXPERT_ACTS}; got {cfg.router_scoring!r}, "
            f"{cfg.expert_act!r}")


@dataclass(frozen=True)
class TrainConfig:
    """Reference hyperparameters: scripts/train_segmenter.py:45-50,143-145."""

    learning_rate: float = 1e-4
    batch_size: int = 4
    epochs: int = 50
    validation_split: float = 0.2
    img_size: int = 256
    seed: int = 0
    # Loss: reference uses BCEWithLogitsLoss only (train_segmenter.py:145).
    # "bce_dice" is the BASELINE.json config-2 variant (Dice+BCE).
    loss: str = "bce"
    dice_weight: float = 0.5
    # MLflow-compatible naming -- byte-compatible with the reference
    # (train_segmenter.py:61-63): experiment + registered model name.
    tracking_uri: str = "file:ml/mlruns"
    experiment_name: str = "Actuator Segmentation"
    registered_model_name: str = "Actuator-Segmenter"
    dataset_dir: str = "ml/datasets/processed"
    checkpoint_dir: str = "ml/checkpoints"
    keep_checkpoints: int = 3
    # checkpoint every N epochs (the final epoch always saves); raise for
    # short-epoch runs where per-epoch state serialization dominates
    checkpoint_every: int = 1
    # TPU-first:
    donate_state: bool = True
    # tensor parallelism: shard conv kernels with >= this many output
    # channels over the mesh "model" axis (see parallel.mesh.tp_param_specs)
    tp_min_channels: int = 256
    # decode threads for the streaming file loader (StreamingBatches)
    loader_workers: int = 4
    # Epoch execution: "auto" runs whole epochs in one lax.scan dispatch
    # when the dataset is in-memory, fits the device beside the job (4 GiB,
    # a constant of training/trainer.py), and no mesh is given (one host
    # fetch per epoch instead of per step); "stream" forces the per-batch
    # loop; "scan" requires the scan path and errors if unavailable.
    epoch_mode: str = "auto"


@dataclass(frozen=True)
class GeometryConfig:
    """Reference: pkg/geometry_utils.py.

    - 50 x-bins, top 5% by y per bin (:119).
    - cubic parametric spline, smoothing s=0.1 (:78).
    - 100 curvature/visualization samples (:83, :146).
    - graceful-zero cutoffs: <100 cloud points (:64), <20 edge points (:69).

    TPU additions (static-shape budget; no reference equivalent):
    - ``max_per_bin``: fixed top-k budget per bin (edge extraction works
      on the dense maps directly -- no cloud-size budget).
    - ``num_ctrl``: number of cubic B-spline basis functions for the
      fixed-knot least-squares fit that replaces FITPACK ``splprep``.
    """

    num_bins: int = 50
    top_k_percent: float = 0.05
    # Fused-kernel dispatch for the non-conv analyzer stages (the Pallas
    # deproject+reduction and B-spline design/curvature kernels under
    # ops/pallas/geometry.py): "auto" runs them on TPU (with the
    # PALLAS_TUNE.json table able to veto per shape) and the XLA reference
    # path elsewhere; "xla"/"pallas" pin one path; "interpret" runs the
    # Pallas interpreter (the CPU test path). The XLA path is the numerics
    # oracle -- the kernels are bitwise-compared against it in
    # tests/test_pallas_geometry.py.
    kernel_impl: str = "auto"
    # Uniform pixel decimation before edge extraction: stride 2 quarters the
    # dominant packed-key sort with curvature error quantified against the
    # scipy oracle in GEOMETRY_PARITY.json (validity cutoffs scale by
    # stride^2 to keep the reference decision boundary). 1 = reference-exact
    # dense semantics.
    stride: int = 1
    spline_degree: int = 3
    # Plays the role of FITPACK's s=0.1 but is a P-spline penalty weight, not
    # a residual target; 1e-3 calibrated against analytic arcs (tests/) to
    # within ~5% of ground-truth curvature.
    spline_smoothing: float = 1e-3
    num_samples: int = 100
    min_cloud_points: int = 100
    min_edge_points: int = 20
    max_per_bin: int = 128
    num_ctrl: int = 16
    default_depth_scale: float = 0.001  # server.py:59


@dataclass(frozen=True)
class ServerConfig:
    """Reference: services/vision_analysis/server.py:50-65,161-179."""

    address: str = "[::]:50051"
    max_workers: int = 10
    model_img_size: int = 256
    default_depth_scale: float = 0.001
    tracking_uri: str = "file:ml/mlruns"
    model_name: str = "Actuator-Segmenter"
    # The reference *documents* loading the "staging" alias (README.md:147)
    # but actually loads "/latest" (server.py:81). We honor the documented
    # intent: try alias first, fall back to latest. See SURVEY.md section 2.1.
    model_alias: str = "staging"
    calibration_path: str = "ml/configs/calibration_data.npz"
    metrics_csv: str = "logs/vision_service_metrics.csv"
    metrics_flush_every: int = 32
    # Prometheus exposition (observability/exposition.py): port for the
    # stdlib `GET /metrics` endpoint, started/stopped with the gRPC server
    # lifecycle. 0 (default) = off; negative = bind an ephemeral port
    # (tests/smoke scripts read it back from servicer.metrics_server.port).
    # The RDP_METRICS_PORT env var overrides this value.
    metrics_port: int = 0
    # Cross-stream micro-batching is OFF by default on purpose: measured on
    # v5e, the U-Net forward's per-frame time RISES with batch (b1 0.86 ->
    # b8 1.39 ms/frame; BENCH notes), so batch-1 chained dispatch is already
    # peak aggregate throughput and batching only adds latency. Concurrent
    # streams aggregate through the device queue instead. >0 enables the
    # dispatcher for workloads where the tradeoff differs.
    batch_window_ms: float = 0.0
    max_batch: int = 8  # per-dispatch cap when micro-batching
    # Batched-dispatch implementation when micro-batching is on:
    # "dense" = one [B, ...] forward (make_batch_analyzer) -- best when the
    # batch fits VMEM; "scan" = one dispatch that lax.scans the frames
    # sequentially (make_scan_batch_analyzer) -- keeps the B=1 working-set
    # residency that dense batching loses on wide 256x256 feature maps
    # (measured anti-scaling: B=4 349.5 vs B=1 501.5 aggregate FPS), while
    # still amortizing per-dispatch overhead. bench.py measures both.
    batch_impl: str = "dense"
    # Pipelined dispatch window: how many batched dispatches may be
    # launched-but-not-completed at once (serving/batching.py). 2 (default)
    # overlaps batch N+1's host staging + H2D + compute with batch N's D2H
    # completion; 1 selects the serial mode (launch only after the previous
    # batch's results reached the host) -- bit-identical results, no
    # overlap. Each in-flight dispatch holds one padded batch of
    # activations on the device, so depth > 2 mostly buys VMEM/HBM
    # pressure, not throughput, unless completion (D2H + fan-out) is the
    # bottleneck. The RDP_INFLIGHT env var overrides this value.
    max_inflight_dispatches: int = 2
    # Multi-chip serving (serving/batching.DeviceRouter over a
    # parallel/mesh "data"-axis mesh): how many devices the dispatcher
    # routes its in-flight window across. 0/1 (default) = single-device
    # dispatch, exactly today's behavior; N > 1 takes the first N devices;
    # -1 takes every available device. Only meaningful when micro-batching
    # is on (batch_window_ms > 0). The RDP_SERVING_CHIPS env var overrides
    # this value.
    serving_mesh: int = 0
    # How a routed dispatch uses the mesh: "round_robin" stages each
    # launched bucket whole onto the least-loaded chip (N independent
    # in-flight windows, one shared completer draining in global launch
    # order -- aggregate FPS scales with chips for single-frame buckets);
    # "sharded" splits one large padded bucket over the mesh "data" axis
    # (NamedSharding(P("data")), per-shard H2D from the pooled staging
    # buffers -- best when single batches are big enough to fill every
    # chip). The RDP_DISPATCH_MODE env var overrides this value.
    dispatch_mode: str = "round_robin"
    # Geometry decimation stride (GeometryConfig.stride). 1 = reference-
    # exact dense semantics, the DEFAULT: serving numerics match the
    # reference out of the box. 2 is the opt-in fast profile -- it quarters
    # the edge-extraction sort (~8% more FPS, BENCH r03: 544 vs 504) with
    # corpus-measured curvature accuracy (GEOMETRY_PARITY.json: 2.8% mean
    # truth error vs 3.3% at stride 1) BUT approximate validity gates:
    # near the thresholds the edge gate (edge_count * s^2 >=
    # min_edge_points) can ACCEPT frames the reference would reject, and
    # the pooled binnable gate (pooled n_valid >= num_bins) can REJECT
    # frames the reference accepts (e.g. 150 native points spread over
    # <50 pooled cells).
    geometry_stride: int = 1
    # Serving precision tier (ops/pallas/quant.py): "f32" = no
    # transformation, bitwise identical to pre-tier serving; "bf16" =
    # activations in bfloat16 with f32 accumulation (params stay f32);
    # "int8" = bf16 activations + per-output-channel symmetric int8 weight
    # quantization of every conv kernel, re-applied per engine generation
    # (hot-reload re-quantizes). Non-f32 tiers are gated at warm-up by the
    # parity thresholds below against f32 goldens. The RDP_PRECISION env
    # var overrides this value.
    precision: str = "f32"
    # Warm-up parity gate for bf16/int8 (ignored at f32): synthetic golden
    # frames are run through BOTH the precision-tier engine and an f32
    # reference analyzer; serving refuses to come up when mean mask IoU
    # falls below the floor or the worst |delta curvature| (1/m) exceeds
    # the ceiling. Thresholds calibrated on the synthetic actuator corpus
    # (tests/test_quant.py measures the real deltas well inside them).
    quant_parity_frames: int = 4
    quant_parity_min_iou: float = 0.90
    quant_parity_max_curv_err: float = 0.5
    # Host-path ingest (serving/ingest.py): decode worker pool width.
    # 0 (default) decodes inline in the handler thread -- byte-for-byte
    # the historical path, the bitwise-parity serial mode. N > 0 moves
    # JPEG/PNG decode onto N pool threads (cv2 releases the GIL in the
    # heavy parts) with per-stream read-ahead, so frame k+1 decodes while
    # frame k rides the device; frames whose deadline is blown in the
    # decode queue are shed BEFORE paying decode cost
    # (rdp_shed_by_deadline_total{point="decode"}). Negative = one worker
    # per CPU. The RDP_DECODE_WORKERS env var overrides this value.
    decode_workers: int = 0
    # How many requests each stream reads ahead into the decode pool
    # (bounds per-stream decoded-frame memory; only meaningful with
    # decode_workers > 0).
    ingest_prefetch: int = 2
    # Host-path egress (serving/egress.py): encode worker pool width.
    # 0 (default) encodes response masks inline in the handler thread --
    # byte-for-byte the historical path, the bitwise-parity serial mode.
    # N > 0 moves legacy PNG encode (cv2 releases the GIL) and the
    # packed/RLE wire encodes onto N pool threads so the handler is free
    # to pump the next frame while this one's response is encoded.
    # Negative = one worker per CPU. The RDP_EGRESS_WORKERS env var
    # overrides this value.
    egress_workers: int = 0
    # When True (default), the batch analyzers end in the fused device
    # pack stage (ops/pipeline.pack_analysis): one [B, P] uint8 D2H per
    # dispatch, results parsed by serving/egress.PackedResult. False
    # restores the pre-pack FrameAnalysis fetch -- the "before" leg of
    # bench_load.py --host-profile's egress comparison.
    egress_pack: bool = True
    # Split JPEG decode (serving/entropy.py + ops/pallas/decode.py):
    # when True, baseline-JPEG color payloads are entropy-decoded on the
    # host to quantized coefficient blocks and the pixel half (dequant +
    # IDCT + chroma upsample + YCbCr->RGB) runs fused ahead of the
    # analyzer on the device -- decoded images never materialize on the
    # host. This is the pure-Python REFERENCE mode; the production split
    # is clients shipping Image.format = 2 coefficient payloads
    # (client.encode_request(fmt="coef")), which the server accepts
    # regardless of this flag. The RDP_ONCHIP_DECODE env var overrides.
    onchip_decode: bool = False
    # Model forward implementation: "auto" = Pallas-fused kernels on TPU,
    # Flax/XLA elsewhere; "flax" / "pallas" force one path (ops/pallas).
    model_forward: str = "auto"
    # Registry poll interval for model hot-reload: when the staging alias
    # (or latest version) moves, a RUNNING server builds + warms the new
    # model off-thread and atomically swaps it in without dropping
    # streams (the reference requires a restart, SURVEY.md section 3.4).
    # <= 0 disables polling.
    reload_poll_s: float = 10.0
    # After a hot-reload swap, how long the OLD engine's batch dispatcher
    # stays alive for in-flight frames before its drain-safe teardown.
    reload_grace_s: float = 10.0
    # -- resilience (robotic_discovery_platform_tpu/resilience/) -----------
    # Registry circuit breaker: after this many consecutive resolve
    # failures the breaker opens and the hot-reload poller fast-fails
    # (serving keeps its current model) until one half-open probe succeeds.
    registry_breaker_failures: int = 3
    # How long the open breaker fast-fails before admitting a probe.
    registry_breaker_reset_s: float = 60.0
    # Per-frame budget a handler thread may block on the batch dispatcher
    # (replaces the old unbounded done.wait()); the gRPC client deadline,
    # when tighter, wins. Generous by default: an UNWARMED engine pays its
    # XLA compile inside the first submit (warmup()/hot-reload warming
    # pre-compiles every bucket precisely so served frames never hit this).
    submit_deadline_s: float = 30.0
    # Load shedding: a submit arriving while this many frames are already
    # queued for the collector fast-fails with RESOURCE_EXHAUSTED instead
    # of growing an unbounded backlog.
    max_backlog: int = 64
    # Collector-thread watchdog poll interval (<= 0 disables): a dead
    # collector error-completes its pending frames and is restarted.
    watchdog_interval_s: float = 1.0
    # Graceful shutdown: how long close() waits for in-flight streams to
    # finish after readiness flips to NOT_SERVING.
    drain_grace_s: float = 5.0
    # -- SLO telemetry (robotic_discovery_platform_tpu/observability/slo.py)
    # End-to-end per-frame latency objective in milliseconds. Frames
    # slower than this -- or shed / errored -- count into
    # rdp_slo_violations_total and drive the error-budget-burn gauge the
    # adaptive scheduler will consume. 0 (default) disables SLO tracking.
    # The RDP_SLO_MS env var overrides this value.
    slo_ms: float = 0.0
    # Error budget: the fraction of frames ALLOWED to miss the objective.
    # Burn = (violating fraction over the sliding window) / budget;
    # sustained burn > 1 means the objective is being breached.
    slo_budget: float = 0.01
    # Sliding-window length (frames) for the burn-rate estimate.
    slo_window: int = 512
    # -- overload control (serving/admission.py, serving/controller.py) -----
    # Backlog overflow policy: "deadline" evicts the queued frame with
    # the least remaining deadline headroom when the cap is hit and sheds
    # frames whose deadline is unmeetable BEFORE staging them; "fifo"
    # restores position-based shedding (reject the newcomer at the cap).
    admission_policy: str = "deadline"
    # Reactive SLO controller (serving/controller.py): consumes the
    # error-budget burn gauge to retune max_inflight / batch window /
    # bucket floor / dispatch mode online, with a brownout ladder under
    # sustained burn > 1. Needs slo_ms > 0 and batch_window_ms > 0. The
    # RDP_CONTROLLER env var overrides this value.
    controller_enabled: bool = False
    # Controller tick period; every decision additionally passes the
    # hysteresis (sustain) and cooldown gates below.
    controller_interval_s: float = 0.5
    # How long burn must hold beyond a threshold before it counts
    # (single slow frames move nothing).
    controller_sustain_s: float = 1.0
    # Minimum spacing between controller actions (one brownout rung or
    # one AIMD step at a time).
    controller_cooldown_s: float = 2.0
    # Hysteresis thresholds around burn = 1: escalate above high,
    # de-escalate/tune below low, dead band between.
    controller_burn_high: float = 1.0
    controller_burn_low: float = 0.5
    # AIMD ceiling for the controller's additive max_inflight increases.
    controller_inflight_cap: int = 8
    # -- drift observability (monitoring/profile.py) ------------------------
    # Online input/prediction drift monitoring: every served frame's free
    # signals (mask coverage, curvatures, depth-validity fraction,
    # segmentation confidence margin) feed per-signal sliding windows
    # scored (PSI / Jensen-Shannon) against a reference profile. Strictly
    # host-side bookkeeping off the compute path.
    drift_enabled: bool = True
    # Reference profile JSON (monitoring/profile.FeatureProfile). Empty =
    # look for drift_profile.json next to the served registry version's
    # weights, else self-baseline on the first drift_baseline_frames
    # frames. The RDP_DRIFT_PROFILE env var overrides this value.
    drift_profile_path: str = ""
    # Sliding live window (frames) each signal is scored over.
    drift_window: int = 256
    # Self-baseline size when no reference profile is available.
    drift_baseline_frames: int = 64
    # Recompute the divergence scores every N observed frames (scoring
    # rebuilds five small histograms; per-frame work is deque appends).
    drift_score_every: int = 16
    # PSI above this counts a signal as drifted (0.25 = the conventional
    # "major shift" boundary; matches DriftConfig.psi_threshold).
    drift_psi_threshold: float = 0.25
    # Hysteresis (mirrors the controller's brownout ladder): a signal
    # must hold above threshold this long before a retrain recommendation
    # fires, and after one fires the monitor stays disarmed until every
    # signal recovers AND this cooldown elapses.
    drift_sustain_s: float = 5.0
    drift_cooldown_s: float = 300.0
    # -- cross-host serving fleet (serving/fleet.py, serving/frontend.py) ---
    # Comma-separated replica endpoints ("host:port,host:port") the fleet
    # front-end fans AnalyzeActuatorPerformance streams out to. Each
    # endpoint is a full per-host replica server (its own chip mesh,
    # reached over localhost/DCN gRPC). Empty = this process is a plain
    # single-host server, exactly today's behavior. The
    # RDP_FLEET_REPLICAS env var overrides this value.
    fleet_replicas: str = ""
    # Membership poll period: every tick each replica's grpc.health.v1
    # status is checked and its stats RPC scraped; a replica reporting
    # NOT_SERVING (or unreachable) drops out of the placement ring
    # exactly like a chip drops out of the chip ring.
    fleet_poll_s: float = 1.0
    # Per-probe deadline for the health check / stats scrape RPCs.
    fleet_probe_timeout_s: float = 1.0
    # Per-replica circuit breaker (resilience/breaker.py): after this
    # many consecutive failed probes or stream-level failures the
    # replica is quarantined out of the ring until a half-open health
    # probe succeeds after fleet_breaker_reset_s.
    fleet_breaker_failures: int = 2
    fleet_breaker_reset_s: float = 5.0
    # How many times one client stream may fail over to another replica
    # (in-flight frames are re-sent to the new replica) before its
    # remaining in-flight frames error-complete instead.
    fleet_max_failovers: int = 3
    # Fleet-level SLO controller: consumes each replica's error-budget
    # burn (scraped via the stats RPC) and de-weights replicas whose
    # burn approaches 1 so new streams shift away BEFORE the replica
    # browns out (the PR 7 control loop lifted one level).
    fleet_controller_enabled: bool = True
    # De-weighting starts when a replica's burn exceeds this (kept below
    # the replica's own brownout trigger at burn = 1).
    fleet_burn_high: float = 0.8
    # Weight floor: a burning replica keeps at least this share of its
    # idle placement weight (0 would starve its burn signal, the same
    # reason brownout rung 3 duty-cycles instead of refusing all).
    fleet_weight_floor: float = 0.1
    # -- elastic membership (lease registration, serving/fleet.py) ----------
    # Elastic membership master switch for the FRONT-END: when on, the
    # front-end runs a LeaseRegistry, accepts Register/Renew/Leave RPCs
    # from self-announcing replicas, and tolerates an empty static
    # replica list (members arrive by lease). Off = static membership,
    # exactly today's behavior. The RDP_FLEET_ELASTIC env var overrides.
    fleet_elastic: bool = False
    # Comma-separated front-end endpoints this REPLICA registers its
    # membership lease with on boot and renews on a TTL ("" = static
    # membership only, exactly today's behavior). The
    # RDP_FLEET_REGISTRARS env var overrides this value.
    fleet_registrars: str = ""
    # Endpoint this replica advertises in its lease ("" = derive
    # localhost:<bound port> at boot). The RDP_FLEET_ADVERTISE env var
    # overrides this value.
    fleet_advertise: str = ""
    # Lease TTL: a member that misses renewals for this long is expired
    # through the health drop-out path (renew cadence is ttl/3). Also
    # the TTL the FRONT-END's LeaseRegistry grants.
    fleet_lease_ttl_s: float = 10.0
    # Comma-separated sibling front-end endpoints this FRONT-END gossips
    # placement + lease state with over the stats RPC ("" = standalone
    # front-end, no gossip). The RDP_FLEET_PEERS env var overrides this.
    fleet_peers: str = ""
    # -- autoscaler (serving/planner.py) ------------------------------------
    # Master switch: when on, the front-end runs the capacity planner
    # against the live /federate roll-ups and acts on its scale-up/down
    # recommendations (spawn a self-registering replica / drain the
    # least-loaded member). Off = static fleet, exactly today's
    # behavior. The RDP_AUTOSCALER env var overrides this value.
    autoscaler_enabled: bool = False
    # Replica-count bounds the autoscaler may move between.
    autoscaler_min_replicas: int = 1
    autoscaler_max_replicas: int = 4
    # PR 7 hysteresis: a scale signal must hold for sustain_s before an
    # action fires, and after any action the scaler sleeps cooldown_s
    # (one action at a time, never a flap).
    autoscaler_sustain_s: float = 5.0
    autoscaler_cooldown_s: float = 30.0
    # Planner headroom: plan capacity so the fleet runs at no more than
    # this fraction of its measured per-replica goodput.
    planner_headroom: float = 0.7
    # Optional LOADBENCH.json path the planner fits per-replica capacity
    # from ("" = try ./LOADBENCH.json, else a conservative default).
    planner_capacity_path: str = ""
    # -- model zoo + statistical multiplexing (serving/zoo.py) --------------
    # Comma-separated zoo roster from the models/variants.py catalog
    # ("seg,multi,aux"): the named engine generations this server holds
    # side by side, each with its own registry entry, precision tier,
    # parity gate, drift reference, and SLO tracker, statistically
    # multiplexed over the shared chip mesh. "" (default) = the legacy
    # single-model server -- the empty roster resolves to the seed
    # binary segmenter alone and the serving path stays bitwise
    # identical to pre-zoo. A wire request's ``model`` field picks the
    # entry per frame ("" = default). The RDP_ZOO_MODELS env var
    # overrides this value.
    zoo_models: str = ""
    # How models map onto chips: "shared" (default) lets the ZooPlacer
    # co-locate models whose measured arrival-rate peaks anti-correlate
    # (AlpaServe-style statistical multiplexing -- each model's burst
    # capacity is every chip its quiet neighbors are not using);
    # "dedicated" pins the static contiguous partition (silicon per
    # model -- the comparison baseline bench_load.py --models measures
    # the multiplexing win against). The RDP_ZOO_PLACEMENT env var
    # overrides this value.
    zoo_placement: str = "shared"
    # ZooPlacer rate-window geometry: per-model arrivals are counted
    # into zoo_rate_interval_s buckets over a zoo_rate_window-bucket
    # sliding window; correlations and placements recompute from it.
    zoo_rate_interval_s: float = 1.0
    zoo_rate_window: int = 60
    # How often a recorded arrival may trigger a re-placement.
    zoo_rebalance_s: float = 5.0
    # Co-location cap: a model extends onto a chip only when every
    # resident's rate correlation with it is below this (unknown /
    # anti-correlated models share freely; synchronized peaks separate).
    zoo_corr_cap: float = 0.25
    # Capped eager warm-up for EXTRA zoo models: how many placements
    # each non-default model pre-compiles (single-frame bucket) at
    # warmup(); the default model keeps its full eager warm. Everything
    # else compiles lazily on its first dispatch -- eagerly warming
    # M x chips x buckets would explode startup. Negative = FULL eager
    # warm per model (every bucket on every placement): slow boot, zero
    # first-burst compile stalls -- what the multimodel bench legs use
    # to measure steady-state multiplexing.
    zoo_eager_warm: int = 1
    # -- chip quarantine (serving/batching.DeviceRouter) --------------------
    # Per-chip dispatch circuit breaker: after this many consecutive
    # dispatch failures on one mesh chip, that chip is quarantined
    # (removed from the ring, health entry NOT_SERVING, in-flight frames
    # failed over to healthy chips) until a half-open probe dispatch
    # succeeds. 0 disables quarantine. The last healthy chip is never
    # quarantined.
    chip_breaker_failures: int = 3
    # How long a quarantined chip fast-fails before a probe dispatch is
    # routed to it.
    chip_breaker_reset_s: float = 15.0


@dataclass(frozen=True)
class RolloutConfig:
    """Drift-triggered retrain/shadow/canary rollout (serving/rollout.py).

    The closed loop over the pieces the platform already has: a drift
    recommendation (monitoring/profile.DriftMonitor) drains the
    least-loaded fleet replica, retrains on its mesh
    (workflows/retraining via parallel/dp.py), shadows the candidate
    behind the live engine, and promotes through the hot-reload swap
    only when every gate below passes -- fail-closed: any failure or
    stage timeout rolls back to the old generation with the fleet
    intact."""

    # Master switch: a server/fleet only drives rollout cycles when this
    # is on. The RDP_ROLLOUT env var overrides this value.
    enabled: bool = False
    # Registry alias the retraining pipeline parks the CANDIDATE under
    # while it is gated (never "staging": the serving alias must not move
    # until promotion).
    candidate_alias: str = "shadow"
    # Fraction of live frames the serving replicas mirror to the
    # candidate during SHADOW (candidate results are never returned to
    # callers; they are diffed against the serving generation's outputs).
    shadow_fraction: float = 0.5
    # Minimum mirrored frames the shadow diff must cover before the gate
    # may pass; fewer by the stage timeout = fail (not "pass by default").
    shadow_min_frames: int = 16
    # Per-replica cap on queued-but-undiffed shadow frames (the mirror
    # hook never blocks a serving handler thread; overflow is dropped
    # and counted, not waited for).
    shadow_queue: int = 64
    # -- promotion gates (ALL must pass; each verdict is counted in
    # rdp_rollout_gate_verdicts_total) ----------------------------------
    # PR 8 parity fixtures: candidate vs the live generation over
    # quant.golden_frames (deterministic synthetic scenes).
    gate_fixture_frames: int = 4
    gate_fixture_min_iou: float = 0.80
    gate_fixture_max_curv_err: float = 1.0
    # Live shadow diff: candidate vs serving outputs on the SAME mirrored
    # frames.
    gate_shadow_min_iou: float = 0.50
    gate_shadow_max_curv_err: float = 1.0
    # Candidate-vs-serving drift score: worst per-signal
    # noise-floor-adjusted PSI between the candidate's and the live
    # engine's signal distributions over the mirrored frames (same
    # frames, so sampling noise is shared; a candidate behaving wildly
    # differently from the model it replaces fails here even if its
    # masks overlap). Note the Laplace smoothing caps PSI near ~1.6 at
    # the default 16-frame window -- 1.0 sits well above same-model
    # noise (measured ~0 in tests) and well below a distribution swap.
    gate_shadow_max_psi: float = 1.0
    # -- per-stage timeouts (a stage exceeding its budget rolls the cycle
    # back; the fleet keeps serving the old generation) -----------------
    drain_timeout_s: float = 30.0
    retrain_timeout_s: float = 1800.0
    shadow_timeout_s: float = 120.0
    promote_timeout_s: float = 60.0


@dataclass(frozen=True)
class ClientConfig:
    """Reference: services/vision_analysis/client.py:43-45."""

    server_address: str = "localhost:50051"
    calibration_path: str = "ml/configs/calibration_data.npz"
    smoothing_window: int = 10
    frame_queue_len: int = 20


@dataclass(frozen=True)
class DriftConfig:
    """Reference: scripts/monitoring/drift_detector.py:16-22,37."""

    metrics_csv: str = "logs/vision_service_metrics.csv"
    baseline_fraction: float = 0.5
    threshold: float = 0.25
    min_rows: int = 50
    report_path: str = "reports/drift_report.png"
    rolling_window: int = 20
    report_dpi: int = 150
    # Distribution-shift gate shared with the online monitor
    # (monitoring/profile.py): baseline-vs-recent PSI above this ALSO
    # flags drift, so a variance blowup with a stable mean is caught.
    # 0.25 is the conventional "major shift" PSI boundary.
    psi_threshold: float = 0.25


@dataclass(frozen=True)
class CalibrationConfig:
    """Reference: scripts/01_calibrate_camera.py:37-38,53-55.

    The reference saves to ml/data/ but reads from ml/configs/ (a real path
    inconsistency, SURVEY.md section 2.1); we unify on ml/configs/.
    """

    checkerboard_cols: int = 9
    checkerboard_rows: int = 7
    square_size_mm: float = 27.0
    min_captures: int = 5
    output_path: str = "ml/configs/calibration_data.npz"


@dataclass(frozen=True)
class CollectConfig:
    """Reference: scripts/02_collect_segmentation_data.py:40-52."""

    output_root: str = "ml/raw_data"
    capture_interval_s: float = 0.5


@dataclass(frozen=True)
class MeshConfig:
    """TPU device-mesh layout (new capability; reference is single-device).

    Axes:
    - ``data``    data parallel (batch sharding, gradient allreduce over ICI)
    - ``model``   tensor parallel (channel sharding of wide conv layers)
    - ``spatial`` spatial/context parallel (H-dimension sharding of activations;
                  XLA inserts halo exchanges for convs)
    Zero/negative sizes mean "infer from available devices".
    """

    data: int = -1
    model: int = 1
    spatial: int = 1


@dataclass(frozen=True)
class PlatformConfig:
    """Root config aggregating every subsystem."""

    camera: CameraConfig = field(default_factory=CameraConfig)
    model: ModelConfig = field(default_factory=ModelConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    geometry: GeometryConfig = field(default_factory=GeometryConfig)
    server: ServerConfig = field(default_factory=ServerConfig)
    rollout: RolloutConfig = field(default_factory=RolloutConfig)
    client: ClientConfig = field(default_factory=ClientConfig)
    drift: DriftConfig = field(default_factory=DriftConfig)
    calibration: CalibrationConfig = field(default_factory=CalibrationConfig)
    collect: CollectConfig = field(default_factory=CollectConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)


def replace(cfg: Any, **updates: Any) -> Any:
    """`dataclasses.replace` re-export (configs are frozen)."""
    return dataclasses.replace(cfg, **updates)


def to_dict(cfg: Any) -> dict:
    return dataclasses.asdict(cfg)


def to_json(cfg: Any) -> str:
    return json.dumps(to_dict(cfg), indent=2, sort_keys=True)


def _coerce(value: str, typ: type) -> Any:
    if typ is bool:
        return value.lower() in ("1", "true", "yes", "on")
    return typ(value)


def from_dict(cls: type, data: dict) -> Any:
    """Rebuild a (possibly nested) config dataclass from a plain dict."""
    known = {f.name for f in dataclasses.fields(cls)}
    unknown = set(data) - known
    if unknown:
        raise ValueError(
            f"unknown config keys for {cls.__name__}: {sorted(unknown)}"
        )
    kwargs = {}
    for f in dataclasses.fields(cls):
        if f.name not in data:
            continue
        v = data[f.name]
        if isinstance(v, dict) and dataclasses.is_dataclass(_resolve(f)):
            kwargs[f.name] = from_dict(_resolve(f), v)
        else:
            kwargs[f.name] = v
    return cls(**kwargs)


def _resolve(f: dataclasses.Field) -> type:
    t = f.type
    if isinstance(t, str):
        # PEP 563 stringified annotations: look up builtins first, then this
        # module (for nested config classes).
        import builtins

        resolved = getattr(builtins, t, None) or globals().get(t)
        if resolved is None:
            raise TypeError(
                f"config field {f.name!r} has unresolvable annotation {t!r}; "
                "use a builtin or a config class defined in this module"
            )
        t = resolved
    return t


def add_flags(parser: argparse.ArgumentParser, cls: type, prefix: str = "") -> None:
    """Register ``--section.field`` flags for every leaf of a config tree."""
    for f in dataclasses.fields(cls):
        t = _resolve(f)
        name = f"{prefix}{f.name}"
        if dataclasses.is_dataclass(t):
            add_flags(parser, t, prefix=f"{name}.")
        else:
            parser.add_argument(f"--{name}", type=str, default=None, help=f"({t.__name__})")


def apply_flags(cfg: Any, args: argparse.Namespace) -> Any:
    """Apply parsed ``--section.field`` overrides onto a frozen config tree."""

    def _apply(node: Any, prefix: str) -> Any:
        updates = {}
        for f in dataclasses.fields(node):
            t = _resolve(f)
            name = f"{prefix}{f.name}"
            if dataclasses.is_dataclass(t):
                updates[f.name] = _apply(getattr(node, f.name), f"{name}.")
            else:
                raw = getattr(args, name, None)
                if raw is not None:
                    updates[f.name] = _coerce(raw, t)
        return dataclasses.replace(node, **updates)

    return _apply(cfg, "")


def parse_config(argv: Sequence[str] | None = None,
                 cls: type = PlatformConfig) -> Any:
    """Build a config from defaults + optional JSON file + CLI overrides."""
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", type=str, default=None, help="JSON config file")
    add_flags(parser, cls)
    args = parser.parse_args(argv)
    cfg = cls()
    if args.config:
        cfg = from_dict(cls, json.loads(Path(args.config).read_text()))
    return apply_flags(cfg, args)
