"""Training benchmark + mIoU parity run: the second north-star obligation
(BASELINE.json: ">= 5x train wall-clock vs the single-device reference at
equal mIoU"; BASELINE.md:24-29).

Three measurements on a fixed synthetic dataset (same generator, seed, and
hyperparameters as bench_reference.py's training anchor -- Adam 1e-4, batch
4, BCE, 256x256, reference: scripts/train_segmenter.py:45-50,143-145):

1. steady-state TPU train-step throughput (chained lax.scan, one fetch --
   see bench.py for the timing method); needs the accelerator and exits
   non-zero without one;
2. an end-to-end `train_model` convergence run recording wall-clock and
   final val mIoU/Dice (the metric the reference never computes, SURVEY.md
   section 2.1 "Trainer");
3. the torch reference-equivalent trained with the same data/config,
   evaluated with the same mIoU -- the parity anchor.

Writes TRAINBENCH.json. Run bench_reference.py first if you also want the
per-stage serving anchor.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO))

N_IMAGES = 64
IMG = 256
BATCH = 4
EPOCHS = 10
SEED = 0


def dataset():
    from robotic_discovery_platform_tpu.training import synthetic

    imgs, masks = synthetic.generate_arrays(N_IMAGES, IMG, IMG, seed=SEED)
    return (imgs.astype(np.float32) / 255.0,
            masks.astype(np.float32) / 255.0)


def miou_np(prob, target, thresh=0.5, eps=1e-7):
    """Same definition as models/losses.mean_iou, in numpy so the torch and
    jax runs are scored identically."""
    pred = (prob > thresh).astype(np.float64)
    t = (target > thresh).astype(np.float64)
    inter = (pred * t).sum()
    union = pred.sum() + t.sum() - inter
    iou_fg = (inter + eps) / (union + eps)
    pred_b, t_b = 1 - pred, 1 - t
    inter_b = (pred_b * t_b).sum()
    union_b = pred_b.sum() + t_b.sum() - inter_b
    iou_bg = (inter_b + eps) / (union_b + eps)
    return float((iou_fg + iou_bg) / 2)


def dice_np(prob, target, thresh=0.5, eps=1e-7):
    pred = (prob > thresh).astype(np.float64)
    t = (target > thresh).astype(np.float64)
    inter = (pred * t).sum()
    return float((2 * inter + eps) / (pred.sum() + t.sum() + eps))


def bench_tpu_step_throughput() -> dict:
    """Chained-scan steady-state train-step rate at the reference batch size
    and at a TPU-efficient batch size."""
    import jax
    import jax.numpy as jnp
    import optax

    from robotic_discovery_platform_tpu.utils import flops as flops_lib
    from robotic_discovery_platform_tpu.utils.platforms import (
        require_accelerator,
    )

    peaks = flops_lib.chip_peaks(
        require_accelerator("bench_train.py tpu").device_kind)

    from robotic_discovery_platform_tpu.models import losses
    from robotic_discovery_platform_tpu.models.unet import build_unet, init_unet
    from robotic_discovery_platform_tpu.training import trainer
    from robotic_discovery_platform_tpu.utils.config import ModelConfig

    model = build_unet(ModelConfig())
    tx = optax.adam(1e-4)
    state = trainer.create_state(model, tx, jax.random.key(0), IMG)
    step = trainer.core_train_step(model, tx, losses.bce_with_logits)
    xs, ys = dataset()

    out = {}
    for batch in (BATCH, 32):
        x = jnp.asarray(xs[:batch])
        y = jnp.asarray(ys[:batch])

        @jax.jit
        def chained(s0, x, y):
            def body(s, _):
                s2, loss = step(s, x, y)
                return s2, loss
            s_final, lossses = jax.lax.scan(body, s0, None, length=50)
            return jnp.sum(lossses)

        t0 = time.perf_counter()
        float(chained(state, x, y))
        compile_s = time.perf_counter() - t0
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            float(chained(state, x, y))
            best = min(best, time.perf_counter() - t0)
        step_ms = best * 1e3 / 50
        step_flops = flops_lib.unet_train_step_flops(batch, IMG)
        out[f"batch{batch}"] = {
            "step_ms": round(step_ms, 3),
            "steps_per_s": round(1000.0 / step_ms, 2),
            "images_per_s": round(batch * 1000.0 / step_ms, 2),
            "compile_s": round(compile_s, 1),
            # conv-only analytic FLOPs (3x forward for fwd+dx+dw) over this
            # device's published bf16 peak (utils/flops.CHIP_PEAKS)
            "mfu": round(flops_lib.mfu(step_flops, step_ms / 1e3, peaks), 4),
        }
    return out


def bench_tpu_convergence(tmp: Path) -> dict:
    import jax

    from robotic_discovery_platform_tpu.training import trainer
    from robotic_discovery_platform_tpu.utils.config import (
        ModelConfig,
        TrainConfig,
    )

    cfg = TrainConfig(
        epochs=EPOCHS, batch_size=BATCH, img_size=IMG, learning_rate=1e-4,
        seed=SEED, validation_split=0.25,
        tracking_uri=f"file:{tmp}/mlruns", checkpoint_dir=f"{tmp}/ckpt",
    )
    res = trainer.train_model(cfg, ModelConfig(), arrays=dataset(),
                              register=False)
    return {
        "backend": jax.default_backend(),
        "epochs": EPOCHS,
        "wall_clock_s": round(res.wall_clock_s, 2),
        "epoch_s": round(res.wall_clock_s / EPOCHS, 2),
        "val_miou": round(res.final_metrics.get("miou", float("nan")), 4),
        "val_dice": round(res.final_metrics.get("dice", float("nan")), 4),
        "best_val_loss": round(res.best_val_loss, 5),
    }


def bench_torch_convergence() -> dict:
    """Reference-equivalent torch training at the same config, scored with
    the same numpy mIoU (reference: scripts/train_segmenter.py:103-210)."""
    import torch

    from bench_reference import build_torch_unet

    xs, ys = dataset()
    n_val = N_IMAGES // 4
    rng = np.random.default_rng(SEED)
    order = rng.permutation(N_IMAGES)
    tr, va = order[n_val:], order[:n_val]
    x = torch.from_numpy(xs.transpose(0, 3, 1, 2))
    y = torch.from_numpy(ys.transpose(0, 3, 1, 2))
    model = build_torch_unet().train()
    opt = torch.optim.Adam(model.parameters(), lr=1e-4)
    loss_fn = torch.nn.BCEWithLogitsLoss()
    t0 = time.perf_counter()
    for _ in range(EPOCHS):
        for i in range(0, len(tr), BATCH):
            idx = tr[i:i + BATCH]
            opt.zero_grad()
            loss = loss_fn(model(x[idx]), y[idx])
            loss.backward()
            opt.step()
    wall = time.perf_counter() - t0
    model.eval()
    probs, targs = [], []
    with torch.no_grad():
        for i in range(0, len(va), BATCH):
            idx = va[i:i + BATCH]
            probs.append(torch.sigmoid(model(x[idx])).numpy())
            targs.append(y[idx].numpy())
    prob = np.concatenate(probs)
    targ = np.concatenate(targs)
    return {
        "backend": "torch-cpu",
        "epochs": EPOCHS,
        "wall_clock_s": round(wall, 2),
        "epoch_s": round(wall / EPOCHS, 2),
        "val_miou": round(miou_np(prob, targ), 4),
        "val_dice": round(dice_np(prob, targ), 4),
    }


def main() -> None:
    import tempfile

    from robotic_discovery_platform_tpu.utils.platforms import (
        enable_compile_cache,
    )

    enable_compile_cache()
    only = sys.argv[1] if len(sys.argv) > 1 else "all"
    out_path = REPO / "TRAINBENCH.json"
    result = {}
    if out_path.exists():
        result = json.loads(out_path.read_text())
    result.setdefault("config", {
        "n_images": N_IMAGES, "img_size": IMG, "batch_size": BATCH,
        "epochs": EPOCHS, "optimizer": "adam(1e-4)", "loss": "bce",
        "dataset": f"training.synthetic.generate_arrays(seed={SEED})",
    })
    if only in ("all", "tpu"):
        result["tpu_step_throughput"] = bench_tpu_step_throughput()
        with tempfile.TemporaryDirectory() as tmp:
            result["tpu_convergence"] = bench_tpu_convergence(Path(tmp))
    if only in ("all", "torch"):
        result["torch_reference"] = bench_torch_convergence()
    if "tpu_convergence" in result and "torch_reference" in result:
        result["speedup_wall_clock"] = round(
            result["torch_reference"]["wall_clock_s"]
            / result["tpu_convergence"]["wall_clock_s"], 2,
        )
        result["miou_delta"] = round(
            result["tpu_convergence"]["val_miou"]
            - result["torch_reference"]["val_miou"], 4,
        )
    result["measured_utc"] = time.strftime(
        "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
    )
    out_path.write_text(json.dumps(result, indent=2) + "\n")
    print(json.dumps(result, indent=2))


if __name__ == "__main__":
    main()
