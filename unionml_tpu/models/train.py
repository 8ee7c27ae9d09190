"""Train-state and step-function factories for the model zoo.

The reference leaves training loops to user code (SURVEY.md §3.1: "the hot
loop lives entirely in the user trainer body"). Here the framework supplies
jit-ready ``step(state, batch) -> (state, metrics)`` functions matching the
:meth:`unionml_tpu.model.Model.train_step` contract, so a zoo model trains
with three lines of app code. Loss math runs in fp32 (bf16 params upcast at
the loss) and gradients are computed by a single ``jax.value_and_grad``
program — XLA fuses the whole step into one executable per shape.
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import linen as nn
from flax.training import train_state


class TrainState(train_state.TrainState):
    """flax TrainState (params + optax state + apply_fn + step counter)."""


def adamw(learning_rate: float, *, weight_decay: float = 0.0,
          b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
          mu_dtype: Optional[Any] = None):
    """AdamW as an explicit optax chain.

    Mathematically identical to ``optax.adamw``, written out because
    ``optax.adamw`` was seen to slow a whole step several-fold under
    buffer donation on TPU (on an older JAX; not re-measured on the
    current stack); the explicit composition compiles clean under
    donated state.

    ``mu_dtype`` (e.g. ``jnp.bfloat16``) stores the FIRST moment at
    reduced precision — 25% of adam-state memory and its HBM traffic.
    The second moment stays fp32 (bf16's 8-bit mantissa distorts
    ``sqrt(v)`` far more than it does ``m``).
    """
    steps = [optax.scale_by_adam(b1=b1, b2=b2, eps=eps, mu_dtype=mu_dtype)]
    if weight_decay:
        steps.append(optax.add_decayed_weights(weight_decay))
    # scale_by_learning_rate accepts floats AND schedules, like optax.adamw
    steps.append(optax.scale_by_learning_rate(learning_rate))
    return optax.chain(*steps)


def create_train_state(
    module: nn.Module,
    example_input: Any,
    *,
    optimizer: Optional[optax.GradientTransformation] = None,
    learning_rate: float = 1e-3,
    weight_decay: float = 0.0,
    seed: int = 0,
    init_kwargs: Optional[dict] = None,
) -> TrainState:
    """Initialize parameters from an example batch and wrap with optax.

    Default optimizer is :func:`adamw` (the donation-safe chain) — the
    optimizer state duplicates the param pytree twice, so under FSDP the
    same partition rules shard it too (ShardingConfig.state_shardings
    walks the whole TrainState).
    """
    params = module.init(
        jax.random.PRNGKey(seed), example_input, **(init_kwargs or {})
    )["params"]
    tx = optimizer or adamw(learning_rate, weight_decay=weight_decay)
    return TrainState.create(apply_fn=module.apply, params=params, tx=tx)


def _accuracy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    return jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))


def resolve_params(state: Any) -> Any:
    """The full apply-tree behind a state-or-params argument.

    Serving/eval surfaces accept either a bare param tree or any
    TrainState; a :class:`~unionml_tpu.models.lora.LoRATrainState` holds
    only the adapters in ``.params``, so its ``full_params()`` (frozen
    base + adapters) is what ``module.apply`` needs.
    """
    if hasattr(state, "full_params"):
        return state.full_params()
    return state.params if hasattr(state, "params") else state


def _bind_frozen(loss_fn: Callable, state: Any) -> Callable:
    """Adapt a loss over FULL params to a state that differentiates a
    subset: for :class:`~unionml_tpu.models.lora.LoRATrainState` the
    trainable tree (``state.params``, lora adapters) is merged over the
    frozen base inside the loss, so ``value_and_grad`` touches only the
    adapters and the optimizer state stays adapter-sized."""
    frozen = getattr(state, "frozen_params", None)
    if frozen is None:
        return loss_fn
    from unionml_tpu.models.lora import merge_param_trees

    return lambda params, batch: loss_fn(merge_param_trees(frozen, params), batch)


class GradOverlap(NamedTuple):
    """How the accumulation scan should overlap gradient collectives
    with compute (docs/performance.md "Overlapped training").

    ``mode="defer"`` keeps GSPMD's automatic collectives but moves the
    *consumption* of microbatch *i*'s (already-reduced) grads into
    iteration *i+1*'s carry-add, giving XLA's collective pipeliner a
    full microbatch of backward compute to hide each all-reduce behind.
    Works under any mesh (dp/fsdp/tensor/…) and is bitwise identical to
    the serial scan (same adds in the same order, plus one exact +0).

    ``mode="shard_map"`` additionally takes the data-axis all-reduce
    manual: the scan runs inside ``shard_map`` over ``axes`` (params
    replicated across them) and issues a deferred
    :func:`~unionml_tpu.parallel.collectives.bucketed_psum` per
    microbatch — one chunked collective stream XLA's async collectives
    can pipeline. Only valid when every non-``axes`` mesh axis is
    trivial (params must be replicated across ``axes``); loss/grad
    trajectories are bitwise identical to serial for power-of-two
    per-device microbatch rows and device counts (exact fp scaling).
    """

    mode: str
    mesh: Any = None
    axes: Tuple[str, ...] = ()
    #: None = bucketed_psum's own DEFAULT_PSUM_BUCKET_BYTES (no stale
    #: duplicate of the canonical constant here)
    bucket_bytes: Optional[int] = None


_GRAD_OVERLAP: contextvars.ContextVar = contextvars.ContextVar(
    "unionml_grad_overlap", default=None
)


@contextlib.contextmanager
def grad_overlap_scope(overlap: Optional[GradOverlap]):
    """Make ``overlap`` the ambient accumulation strategy: any
    :func:`accumulated_value_and_grad` TRACED inside this scope (i.e.
    any zoo-factory step compiled by a trainer loop running in it)
    adopts it without the step author plumbing a parameter through.
    The trainer loops open this scope for ``overlap_grads=True``; the
    jit cache keys on the ambient overlap so serial and overlapped
    executables never alias."""
    token = _GRAD_OVERLAP.set(overlap)
    try:
        yield overlap
    finally:
        _GRAD_OVERLAP.reset(token)


def current_grad_overlap() -> Optional[GradOverlap]:
    """The ambient :class:`GradOverlap` (None = serial accumulation)."""
    return _GRAD_OVERLAP.get()


def _zeros_like_shapes(tree: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, jnp.float32), tree
    )


def accumulated_value_and_grad(
    loss_fn: Callable,
    params: Any,
    batch: Any,
    *,
    overlap: Optional[GradOverlap] = None,
) -> Tuple[Tuple[jnp.ndarray, Any], Any]:
    """Mean (loss, aux) and grads of ``loss_fn(params, microbatch)`` over
    the leading microbatch axis of ``batch``, via one ``lax.scan``.

    The gradient-accumulation core (SURVEY.md §7 layer 3): ``batch``
    leaves are ``[n_micro, micro_batch, ...]``; each scan step runs one
    microbatch forward+backward and adds into an fp32 grad accumulator,
    so HBM holds one microbatch's activations at a time while the
    *effective* batch is ``n_micro`` times larger. With equal microbatch
    sizes and mean-style losses, the averaged grads equal the one-shot
    big-batch grads up to float summation order (tested). ``aux`` must be
    a pytree of scalars (metrics) — it is averaged the same way.

    ``overlap`` (default: the ambient :func:`grad_overlap_scope`, set by
    ``run_step_trainer(overlap_grads=True)``) restructures the scan so
    gradient collectives overlap the next microbatch's backward — see
    :class:`GradOverlap`; every mode is loss-trajectory-identical to
    the serial scan.
    """
    if overlap is None:
        overlap = _GRAD_OVERLAP.get()
    if overlap is not None and overlap.mode == "shard_map":
        return _shard_map_accumulated(loss_fn, params, batch, overlap)
    defer = overlap is not None and overlap.mode == "defer"
    if overlap is not None and overlap.mode not in ("defer", "shard_map"):
        raise ValueError(
            f"unknown GradOverlap mode {overlap.mode!r}: "
            "expected 'defer' or 'shard_map'"
        )

    vg = jax.value_and_grad(loss_fn, has_aux=True)
    n = jax.tree_util.tree_leaves(batch)[0].shape[0]
    first = jax.tree_util.tree_map(lambda x: x[0], batch)
    # trace-time structure probe: zero accumulators for loss/aux/grads
    (loss_s, aux_s), grad_s = jax.eval_shape(vg, params, first)
    zeros = _zeros_like_shapes

    if defer:
        # deferred consumption: iteration i adds iteration i-1's grads
        # (the `pending` carry) into the accumulator BEFORE computing
        # its own, so the collectives GSPMD attached to microbatch i's
        # grads are not needed until a whole microbatch of backward
        # compute later — the window XLA's collective pipeliner hides
        # them in. Same adds in the same order as the serial scan (plus
        # an exact leading +0): bitwise-identical trajectories.
        def body(carry, microbatch):
            loss_acc, aux_acc, grad_acc, pending = carry
            with jax.named_scope("grad_accumulate"):
                grad_acc = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(jnp.float32), grad_acc, pending
                )
            (loss, aux), grads = vg(params, microbatch)
            with jax.named_scope("grad_accumulate"):
                loss_acc = loss_acc + loss.astype(jnp.float32)
                aux_acc = jax.tree_util.tree_map(
                    lambda a, b: a + jnp.asarray(b, jnp.float32), aux_acc, aux
                )
            return (loss_acc, aux_acc, grad_acc, grads), None

        with jax.named_scope("grad_accumulate"):
            pending0 = jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), grad_s
            )
            init = (zeros(loss_s), zeros(aux_s), zeros(grad_s), pending0)
        (loss, aux, grads, pending), _ = jax.lax.scan(body, init, batch)
        with jax.named_scope("grad_accumulate"):
            grads = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), grads, pending
            )
    else:
        def body(carry, microbatch):
            loss_acc, aux_acc, grad_acc = carry
            (loss, aux), grads = vg(params, microbatch)
            with jax.named_scope("grad_accumulate"):
                loss_acc = loss_acc + loss.astype(jnp.float32)
                aux_acc = jax.tree_util.tree_map(
                    lambda a, b: a + jnp.asarray(b, jnp.float32), aux_acc, aux
                )
                grad_acc = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(jnp.float32), grad_acc, grads
                )
            return (loss_acc, aux_acc, grad_acc), None

        with jax.named_scope("grad_accumulate"):
            init = (zeros(loss_s), zeros(aux_s), zeros(grad_s))
        (loss, aux, grads), _ = jax.lax.scan(body, init, batch)
    mean = lambda t: jax.tree_util.tree_map(lambda x: x / n, t)  # noqa: E731
    with jax.named_scope("grad_accumulate"):
        grads = jax.tree_util.tree_map(
            lambda g, p: (g / n).astype(p.dtype), grads, params
        )
        return (loss / n, mean(aux)), grads


def _shard_map_accumulated(
    loss_fn: Callable, params: Any, batch: Any, overlap: GradOverlap
) -> Tuple[Tuple[jnp.ndarray, Any], Any]:
    """The manual-collective accumulation: scan inside ``shard_map``
    over the batch axes, per-microbatch deferred ``bucketed_psum``.

    Params are replicated across ``overlap.axes`` (the pure-DP layout;
    the trainer only selects this mode when every other mesh axis is
    trivial), each device runs ``loss_fn`` on its local microbatch
    rows, and the data-axis all-reduce of microbatch *i*'s grads is
    issued in iteration *i* but consumed in *i+1* — an explicit,
    chunked collective stream for XLA's async collectives to pipeline
    behind the next backward.
    """
    from jax import lax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from unionml_tpu.parallel.collectives import bucketed_psum

    axes = tuple(overlap.axes)
    if overlap.mesh is None or not axes:
        raise ValueError(
            "GradOverlap(mode='shard_map') needs a mesh and at least one "
            "reduce axis (the batch axes the grads all-reduce over)"
        )
    axis_arg = axes if len(axes) > 1 else axes[0]

    def local(params, batch):
        vg = jax.value_and_grad(loss_fn, has_aux=True)
        n = jax.tree_util.tree_leaves(batch)[0].shape[0]
        first = jax.tree_util.tree_map(lambda x: x[0], batch)
        (loss_s, aux_s), grad_s = jax.eval_shape(vg, params, first)
        zeros = _zeros_like_shapes

        def body(carry, microbatch):
            loss_acc, aux_acc, grad_acc, pending = carry
            # consume the PREVIOUS microbatch's reduced grads first …
            with jax.named_scope("grad_accumulate"):
                grad_acc = jax.tree_util.tree_map(
                    lambda a, g: a + g, grad_acc, pending
                )
            (loss, aux), grads = vg(params, microbatch)
            # … and issue this one's all-reduce, bucketed so the chunks
            # pipeline; its result is not needed until the next
            # iteration's carry-add
            with jax.named_scope("grad_accumulate"):
                bucket_kw = (
                    {} if overlap.bucket_bytes is None
                    else {"bucket_bytes": overlap.bucket_bytes}
                )
                reduced = bucketed_psum(
                    jax.tree_util.tree_map(
                        lambda g: g.astype(jnp.float32), grads
                    ),
                    axis_arg, **bucket_kw,
                )
                loss_acc = loss_acc + lax.pmean(
                    loss.astype(jnp.float32), axis_arg
                )
                aux_acc = jax.tree_util.tree_map(
                    lambda a, b: a + lax.pmean(
                        jnp.asarray(b, jnp.float32), axis_arg
                    ),
                    aux_acc, aux,
                )
            return (loss_acc, aux_acc, grad_acc, reduced), None

        with jax.named_scope("grad_accumulate"):
            init = (zeros(loss_s), zeros(aux_s), zeros(grad_s), zeros(grad_s))
        (loss, aux, grads, pending), _ = jax.lax.scan(body, init, batch)
        with jax.named_scope("grad_accumulate"):
            grads = jax.tree_util.tree_map(lambda a, g: a + g, grads, pending)
            ndev = lax.psum(1, axis_arg)
            mean = lambda t: jax.tree_util.tree_map(  # noqa: E731
                lambda x: x / n, t
            )
            # /(n*ndev) in ONE division: ndev is a power of two on real
            # meshes, so the extra scale vs the serial path's /n is exact
            grads = jax.tree_util.tree_map(
                lambda g, p: (g / (n * ndev)).astype(p.dtype), grads, params
            )
            return (loss / n, mean(aux)), grads

    fn = shard_map(
        local, mesh=overlap.mesh,
        in_specs=(P(), P(None, axes if len(axes) > 1 else axes[0])),
        out_specs=((P(), P()), P()),
        check_vma=False,
    )
    return fn(params, batch)


def masked_cross_entropy(
    logits: jnp.ndarray, targets: jnp.ndarray, *, ignore_id: int = -100
) -> jnp.ndarray:
    """Mean CE over positions where ``targets != ignore_id`` (fp32 math).

    Shared by the serial :func:`lm_step` and the pipelined trainer
    (models/pipeline_lm.py) so their losses cannot drift apart.
    """
    logits = logits.astype(jnp.float32)
    mask = (targets != ignore_id).astype(jnp.float32)
    safe = jnp.where(targets == ignore_id, 0, targets)
    ce = optax.softmax_cross_entropy_with_integer_labels(logits, safe)
    return (ce * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def classification_step(module: nn.Module, *, accumulate_steps: int = 1) -> Callable:
    """softmax-CE step for (features, int_labels) batches (MLP/ViT/BERT-cls).

    ``accumulate_steps > 1``: the step expects batches with a leading
    microbatch axis (``[n_micro, micro_batch, ...]`` — the trainer's
    ``accumulate_steps`` feeds this shape) and applies ONE optimizer
    update from the grad mean over the scan (gradient accumulation).
    """

    def loss_fn(params, microbatch):
        features, labels = microbatch
        logits = module.apply({"params": params}, features)
        with jax.named_scope("loss"):
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), labels
            ).mean()
            return loss, {"accuracy": _accuracy(logits, labels)}

    def step(state: TrainState, batch: Tuple[Any, Any]):
        bound = _bind_frozen(loss_fn, state)
        if accumulate_steps > 1:
            (loss, aux), grads = accumulated_value_and_grad(
                bound, state.params, batch
            )
        else:
            (loss, aux), grads = jax.value_and_grad(bound, has_aux=True)(
                state.params, batch
            )
        with jax.named_scope("optimizer"):
            state = state.apply_gradients(grads=grads)
        return state, {"loss": loss, "accuracy": aux["accuracy"]}

    return step


def lm_step(
    module: nn.Module,
    *,
    ignore_id: int = -100,
    aux_loss_weight: float = 0.01,
    accumulate_steps: int = 1,
) -> Callable:
    """Next-token LM step: batch is token ids [B, S]; loss over shifted pairs.

    Also accepts ``(tokens, labels)`` for masked-LM/fine-tune batches where
    labels carry ``ignore_id`` at unsupervised positions.

    MoE modules sow per-layer load-balancing losses into the
    ``aux_losses`` collection (ops/moe.py); their layer-mean is added to
    the CE loss scaled by ``aux_loss_weight`` and reported as the
    ``aux_loss`` metric (0 for dense models).

    ``accumulate_steps > 1``: gradient accumulation — batches carry a
    leading microbatch axis ([n_micro, micro_batch, S]), grads are
    scan-accumulated in fp32, and the optimizer updates once. This is
    the HBM-bound long-context knob: the 16k-context leg runs microbatch
    1 per device; accumulation restores the effective batch without the
    activation memory.
    """

    def loss_fn(params, microbatch):
        if isinstance(microbatch, tuple):
            inputs, targets = microbatch
        else:
            with jax.named_scope("loss"):
                inputs, targets = microbatch[:, :-1], microbatch[:, 1:]
        logits, mods = module.apply(
            {"params": params}, inputs, mutable=["aux_losses"]
        )
        with jax.named_scope("loss"):
            ce_loss = masked_cross_entropy(logits, targets, ignore_id=ignore_id)
            sown = jax.tree_util.tree_leaves(mods.get("aux_losses", {}))
            aux = (
                sum(v.astype(jnp.float32) for v in sown) / len(sown)
                if sown
                else jnp.float32(0.0)
            )
            return ce_loss + aux_loss_weight * aux, {"ce": ce_loss, "aux": aux}

    def step(state: TrainState, batch):
        bound = _bind_frozen(loss_fn, state)
        if accumulate_steps > 1:
            (_, aux), grads = accumulated_value_and_grad(
                bound, state.params, batch
            )
        else:
            (_, aux), grads = jax.value_and_grad(bound, has_aux=True)(
                state.params, batch
            )
        with jax.named_scope("optimizer"):
            state = state.apply_gradients(grads=grads)
        loss, aux_loss = aux["ce"], aux["aux"]
        with jax.named_scope("loss"):
            perplexity = jnp.exp(loss)
        return state, {"loss": loss, "perplexity": perplexity, "aux_loss": aux_loss}

    return step


def make_evaluator(module: nn.Module) -> Callable:
    """Build an @model.evaluator-compatible fn: (state, features, labels) -> acc."""

    @jax.jit
    def _acc(params, features, labels):
        logits = module.apply({"params": params}, features)
        return _accuracy(logits, labels)

    def evaluator(state: Any, features: Any, labels: Any) -> float:
        return float(_acc(resolve_params(state), jnp.asarray(features), jnp.asarray(labels)))

    return evaluator


def make_predictor(module: nn.Module) -> Callable:
    """Build an @model.predictor-compatible fn: argmax class prediction."""

    @jax.jit
    def _predict(params, features):
        return jnp.argmax(module.apply({"params": params}, features), axis=-1)

    def predictor(state: Any, features: Any) -> Any:
        return _predict(resolve_params(state), jnp.asarray(features))

    return predictor
