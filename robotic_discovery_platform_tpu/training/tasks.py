"""What a retraining job trains: the seam between ``trainer.train_model``
and a model family.

``train_model`` owns the job (phases and spans, the kept jitted runners,
the whole-epoch ``lax.scan``, optax Adam, checkpoints, tracking, registry);
a task owns what differs between families (the U-Net segmenters and three
language models, one trained by block diffusion, two by next-token
prediction): how the model is built from its configuration, the initial
state, how a data set is staged, the loss of a batch, the evaluation
metrics of a batch, which parameters and metrics are logged, and what the
registry is handed. A task is found from the type of the model
configuration (:func:`task_for`) and, for a logged model, from the name it
wrote beside the weights (:func:`task_named`).

Every task has:

``name``
    its label (runner cache key, the ``family`` file of a logged model).
``config_type``
    the model configuration's dataclass.
``val_logged``
    evaluation metrics logged each epoch as ``val_<name>`` beside
    ``val_loss``.
``build(model_cfg)``, ``make_loss(cfg)``, ``memo_fields``
    model and loss; ``memo_fields`` names every field of the ``TrainConfig``
    that the loss closes over (the kept runners are keyed on their values).
``for_mesh(model_cfg)``
    the configuration a job under a device mesh trains.
``init_variables(model, rng, cfg)`` -> ``(params, batch_stats)``
``file_data(cfg)``, ``prepare(arrays, cfg)`` -> ``(xs, ys)``
    the streamed data set of ``cfg.dataset_dir``; an in-memory data set
    normalised for the step (host arrays, first axis the samples). Rows a
    step takes as they are (token ids) are plain arrays and reach it
    untouched; integer rows a step takes as floats may be handed on as
    ``data.IntegerRows``, the rows with the task's rule for their floats,
    which the job makes where it holds them.
``train_loss(model, loss_fn, params, state, x, y)``
    -> ``(loss, (collection updates, aux))``, differentiated in ``params``;
    ``aux`` is a dict of per-step numbers the epoch averages ({} for none).
``evaluate(model, loss_fn, state, x, y)`` -> dict with ``"loss"``
``run_params(cfg, model_cfg)`` -> parameters logged beside the job's own
``observe(out, n_steps, batch_size, seconds, sample_shape)``
    the epoch's averaged ``aux`` into the family's counters.
``variables(params, stats)`` / ``template(model)``
    what ``tracking.log_model`` is handed, and an abstract tree of it for
    loading one back.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from robotic_discovery_platform_tpu.models import losses as losses_lib
from robotic_discovery_platform_tpu.observability import instruments as obs
from robotic_discovery_platform_tpu.training import data as data_lib
from robotic_discovery_platform_tpu.utils.config import (
    HYBRID_KINDS, BlockDiffLMConfig, CausalLMConfig, HybridLMConfig,
    ModelConfig, TrainConfig)


class UNetTask:
    """The segmenter: images and masks, BCE (+ Dice), mIoU / Dice."""

    name = "unet"
    config_type = ModelConfig
    val_logged = ("miou", "dice")

    def build(self, model_cfg: ModelConfig):
        from robotic_discovery_platform_tpu.models.unet import build_unet

        return build_unet(model_cfg)

    def make_loss(self, cfg: TrainConfig):
        return losses_lib.make_loss_fn(cfg.loss, cfg.dice_weight)

    memo_fields = ("loss", "dice_weight")

    def for_mesh(self, model_cfg: ModelConfig) -> ModelConfig:
        # the custom-VJP Pallas convs carry no pjit partitioning rules;
        # under a mesh the nn.Conv/XLA path is the sharding-correct one
        if model_cfg.conv_impl == "flax":
            return model_cfg
        from robotic_discovery_platform_tpu.utils.config import replace

        return replace(model_cfg, conv_impl="flax")

    def init_variables(self, model, rng, cfg: TrainConfig):
        from robotic_discovery_platform_tpu.models.unet import init_unet

        variables = init_unet(model, rng, cfg.img_size)
        return variables["params"], variables.get("batch_stats", {})

    def file_data(self, cfg: TrainConfig):
        # file-backed: decoded batch-by-batch by StreamingBatches, so
        # dataset size is bounded by disk, not host RAM
        return data_lib.PairedSegmentationData(cfg.dataset_dir, cfg.img_size)

    def prepare(self, arrays, cfg: TrainConfig):
        del cfg
        xs, ys = arrays
        # normalize to ndarrays once (dtype preserved, so integer inputs
        # are normalized identically whether they arrive as arrays or
        # lists): the index-array batching needs fancy indexing
        if not hasattr(xs, "nbytes"):
            xs = np.asarray(xs)
        if not hasattr(ys, "nbytes"):
            ys = np.asarray(ys)
        # Integer inputs get the same float normalization the file loader
        # applies (data.PairedSegmentationData.load): images /255, masks
        # /255 when 0/255-coded but a plain cast when already {0, 1} class
        # indices -- dividing those by 255 would silently train against
        # ~0.004 targets. Besides the wrong scale, u8 arrays reaching the
        # jitted train step trip an XLA CPU space_to_batch crash on conv
        # backprop (e.g. synthetic.generate_arrays' raw uint8 output).
        # The rule is stated here and every check made here; the floats of
        # rows narrower than float32 are made where the job holds them
        # (data.float_rows): on the device, for a resident data set.
        if not np.issubdtype(xs.dtype, np.floating):
            xs = data_lib.float_rows(xs, unit=True)
        if not np.issubdtype(ys.dtype, np.floating):
            coded_255 = np.max(ys, initial=0) > 1
            # only the file loader's 0/255 coding gets the /255 path;
            # any other integer coding (class indices {0,2}, 0..K
            # multi-class labels) would silently become ~K/255 targets,
            # so reject it loudly instead of training against noise
            # (one O(N) pass; the sort for the message only on error)
            if coded_255 and not ((ys == 0) | (ys == 255)).all():
                raise ValueError(
                    "integer masks must be coded {0,1} or {0,255}; got "
                    f"values {np.unique(ys)[:8].tolist()}"
                )
            ys = data_lib.float_rows(ys, unit=bool(coded_255))
        return xs, ys

    def train_loss(self, model, loss_fn, params, state, x, y):
        variables = {"params": params}
        with jax.named_scope("rdp.forward"):
            if state.batch_stats:
                variables["batch_stats"] = state.batch_stats
                logits, updates = model.apply(
                    variables, x, train=True, mutable=["batch_stats"]
                )
            else:
                logits, updates = (
                    model.apply(variables, x, train=True), {})
        with jax.named_scope("rdp.loss"):
            return loss_fn(logits, y), (updates, {})

    def evaluate(self, model, loss_fn, state, x, y):
        variables = {"params": state.params}
        if state.batch_stats:
            variables["batch_stats"] = state.batch_stats
        logits = model.apply(variables, x, train=False)
        return {
            "loss": loss_fn(logits, y),
            "miou": losses_lib.mean_iou(logits, y),
            "dice": losses_lib.dice_coefficient(logits, y),
            "accuracy": losses_lib.pixel_accuracy(logits, y),
        }

    def run_params(self, cfg: TrainConfig, model_cfg: ModelConfig) -> dict:
        # exact reference param-name surface (train_segmenter.py:119-128)
        return {"image_size": cfg.img_size, "loss": cfg.loss,
                "model": "UNet", "bilinear": model_cfg.bilinear,
                "base_features": model_cfg.base_features}

    def observe(self, out, n_steps, batch_size, seconds, sample_shape):
        del out, n_steps, batch_size, seconds, sample_shape

    def variables(self, params, stats) -> dict:
        variables = {"params": params}
        if stats:
            variables["batch_stats"] = stats
        return variables

    def template(self, model):
        from robotic_discovery_platform_tpu.models.unet import init_unet

        return init_unet(model, jax.random.key(0))


class _SparseDecoderTask:
    """What the language-model tasks share: a sparse-expert decoder as
    one chip's share of an expert-parallel job (``models/moe``), trained on
    a resident token data set, ``xs`` ``[n, L]`` int32 sequences; a seeded
    start a reference can re-derive; the routing's counters."""

    def make_loss(self, cfg: TrainConfig):
        """Nothing of the configuration shapes the loss (the arithmetic is
        the model module's)."""
        return None

    memo_fields = ()

    def for_mesh(self, model_cfg):
        raise ValueError(
            f"the {self.name} task trains on one device: experts are not "
            "exchanged across chips")

    def init_variables(self, model, rng, cfg: TrainConfig):
        """The job's weights from its seed, by a rule a reference can
        re-derive: ``model.init`` (``moe.seeded_params``) under one ``jit``
        on ``jax.random.key(cfg.seed, impl="rbg")``, the device's
        counter-based generator. (Under the default threefry keys the
        program that draws a leaf of 1.5e8 elements takes the TPU compiler 9
        to 15 s, a shape; a job that starts from nothing paid a minute for
        its weights.)"""
        del rng
        return jax.jit(model.init)(
            jax.random.key(cfg.seed, impl="rbg")), {}

    def file_data(self, cfg: TrainConfig):
        raise ValueError(f"the {self.name} task trains on an in-memory "
                         "token data set (arrays=(tokens, None))")

    def _sequences(self, model, tokens):
        if tokens.shape[1] != model.cfg.seq_len:
            raise ValueError(f"sequences of {tokens.shape[1]} tokens for a "
                             f"model of seq_len {model.cfg.seq_len}")

    def _routing(self, sizes) -> dict:
        """A step's ``aux`` from the rows each layer's held experts took."""
        sizes = sizes.astype(jnp.float32)       # [layers, experts held]
        return {"routed_rows": jnp.sum(sizes),
                "expert_load": jnp.sum(sizes, axis=0)}

    def observe(self, out, n_steps, batch_size, seconds, sample_shape):
        load = np.asarray(out["expert_load"], np.float64)
        rows = float(out["routed_rows"]) * n_steps
        obs.MOE_ROUTED_ROWS.inc(rows)
        # on the epoch's record of the job's timeline, beside its steps
        obs.TRAIN_PHASES.annotate(routed_rows=round(rows))
        if load.mean() > 0:
            obs.MOE_LOAD_RATIO.set(float(load.max() / load.mean()))
        if seconds > 0:
            obs.TRAIN_TOKENS_RATE.set(
                n_steps * batch_size * sample_shape[0] / seconds)

    def variables(self, params, stats) -> dict:
        del stats
        return {"params": params}

    def template(self, model):
        return jax.eval_shape(
            lambda: {"params": model.init(jax.random.key(0))})


class BlockDiffLMTask(_SparseDecoderTask):
    """A sparse-expert decoder trained by block diffusion
    (``models/blockdiff_lm``). There are no targets beside the sequences;
    ``ys`` carries, once a sequence, the seed the job's noise is drawn
    from. The noise of a step is a function of seed and step count
    (``data.block_diffusion_noise``), and a seed that reached the step as a
    constant would make every job's compiled programs its own: as data, one
    program serves every seed, in this process (the kept runners) and from
    the compile cache in the next."""

    name = "blockdiff_lm"
    config_type = BlockDiffLMConfig
    val_logged = ("masked_accuracy",)

    def build(self, model_cfg: BlockDiffLMConfig):
        from robotic_discovery_platform_tpu.models.blockdiff_lm import (
            build_blockdiff_lm)

        return build_blockdiff_lm(model_cfg)

    def prepare(self, arrays, cfg: TrainConfig):
        tokens = data_lib.token_arrays(arrays[0])
        return tokens, np.full(len(tokens), cfg.seed, np.int32)

    def _noised(self, model, seeds, stream, index, tokens):
        c = model.cfg
        self._sequences(model, tokens)
        # a batch is of one job: every row carries the same seed
        return data_lib.block_diffusion_noise(
            seeds[0], stream, index, tokens.shape[0], c.seq_len,
            c.block_length)

    def train_loss(self, model, loss_fn, params, state, x, y):
        from robotic_discovery_platform_tpu.models.blockdiff_lm import (
            diffusion_loss)
        import optax

        del loss_fn
        steps_taken = optax.tree_utils.tree_get(state.opt_state, "count")
        masked, t = self._noised(model, y, data_lib.TRAIN_NOISE,
                                 steps_taken, x)
        logits, sizes = model.apply(params, x, masked)
        with jax.named_scope("rdp.loss"):
            loss = diffusion_loss(logits, x, masked, t)
        return loss, ({}, self._routing(sizes))

    def evaluate(self, model, loss_fn, state, x, y):
        from robotic_discovery_platform_tpu.models.blockdiff_lm import (
            diffusion_loss)

        del loss_fn
        masked, t = self._noised(model, y, data_lib.EVAL_NOISE, 0, x)
        logits, _ = model.apply(state.params, x, masked)
        hit = (jnp.argmax(logits, axis=-1) == x) & masked
        return {"loss": diffusion_loss(logits, x, masked, t),
                "masked_accuracy": jnp.sum(hit) / jnp.maximum(
                    jnp.sum(masked), 1)}

    def run_params(self, cfg: TrainConfig, model_cfg) -> dict:
        c = model_cfg
        return {"model": "BlockDiffLM", "loss": "block_diffusion",
                "seq_len": c.seq_len, "block_length": c.block_length,
                "num_layers": c.num_layers, "hidden_size": c.hidden_size,
                "experts_held": f"{c.experts_held}/{c.num_experts}",
                "vocab_size": c.vocab_size}


class CausalLMTask(_SparseDecoderTask):
    """A sparse-expert decoder of window and full attention layers trained
    by next-token prediction (``models/causal_lm``). The targets are the
    sequences themselves, shifted by one inside the step, and the step
    draws no noise: ``ys`` is one zero a sequence, which the job's feed
    carries and nothing reads."""

    name = "causal_lm"
    config_type = CausalLMConfig
    val_logged = ("token_accuracy",)

    def build(self, model_cfg: CausalLMConfig):
        from robotic_discovery_platform_tpu.models.causal_lm import (
            build_causal_lm)

        return build_causal_lm(model_cfg)

    def prepare(self, arrays, cfg: TrainConfig):
        del cfg
        tokens = data_lib.token_arrays(arrays[0])
        return tokens, np.zeros(len(tokens), np.int32)

    def train_loss(self, model, loss_fn, params, state, x, y):
        del loss_fn, state, y
        self._sequences(model, x)
        loss, _, sizes = model.loss(params, x)
        return loss, ({}, self._routing(sizes))

    def evaluate(self, model, loss_fn, state, x, y):
        del loss_fn, y
        self._sequences(model, x)
        loss, accuracy, _ = model.loss(state.params, x, with_hits=True)
        return {"loss": loss, "token_accuracy": accuracy}

    def run_params(self, cfg: TrainConfig, model_cfg) -> dict:
        c = model_cfg
        return {"model": "CausalLM", "loss": "next_token",
                "seq_len": c.seq_len, "sliding_window": c.sliding_window,
                "num_layers": c.num_layers, "hidden_size": c.hidden_size,
                "experts_held": f"{c.experts_held}/{c.num_experts}",
                "vocab_size": c.vocab_size}


class HybridLMTask(CausalLMTask):
    """A decoder of layers of one branch each, state-space mixers, gated
    short convolutions, attention, dense MLPs and experts, under an untied
    or a tied head (``models/hybrid_lm``), trained by next-token prediction
    as
    :class:`CausalLMTask` trains its own: the model object answers the same
    ``loss``, and the rows counted are the expert layers'."""

    name = "hybrid_lm"
    config_type = HybridLMConfig

    def build(self, model_cfg: HybridLMConfig):
        from robotic_discovery_platform_tpu.models.hybrid_lm import (
            build_hybrid_lm)

        return build_hybrid_lm(model_cfg)

    def run_params(self, cfg: TrainConfig, model_cfg) -> dict:
        c = model_cfg
        letters = {kind: letter for letter, kind in HYBRID_KINDS.items()}
        return {"model": "HybridLM", "loss": "next_token",
                "seq_len": c.seq_len,
                "layer_pattern": "".join(
                    letters[kind] for kind in c.layer_pattern),
                "num_layers": c.num_layers, "hidden_size": c.hidden_size,
                "experts_held": f"{c.experts_held}/{c.num_experts}",
                "vocab_size": c.vocab_size}


UNET = UNetTask()
BLOCKDIFF_LM = BlockDiffLMTask()
CAUSAL_LM = CausalLMTask()
HYBRID_LM = HybridLMTask()
TASKS = (UNET, BLOCKDIFF_LM, CAUSAL_LM, HYBRID_LM)


def task_for(model_cfg):
    """The task that trains a model configuration, by its type."""
    for task in TASKS:
        if isinstance(model_cfg, task.config_type):
            return task
    raise TypeError(f"no training task for {type(model_cfg).__name__}")


def task_named(name: str):
    for task in TASKS:
        if task.name == name:
            return task
    raise KeyError(f"no training task named {name!r}")
