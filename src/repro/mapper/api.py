"""One-call mapping entry points for the repo's model zoo.

``map_arch("llama3-8b", kind="train")`` traces the arch's real step
function (abstract params/opt-state/batch — nothing is allocated, so the
full 32B configs map fine on a laptop) and compiles it into a placed,
cost-rolled static schedule. ``map_lenet`` does the same for the paper's
own benchmark network, whose schedule is small enough to *execute*
numerically with ``repro.mapper.executor``.

``compile_arch`` / ``compile_lenet`` go one step further: schedule ->
:func:`repro.mapper.compile.compile_schedule` -> a jittable,
differentiable ``CompiledProgram`` running the step *through the
placement* (smoke configs recommended for archs you intend to actually
call — the full 32B programs trace, but allocating their params is on
you).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import configs
from repro.configs.base import ShapeSpec
from repro.mapper import compile as compile_mod
from repro.mapper import placement as placement_mod
from repro.mapper import schedule as schedule_mod
from repro.mapper.hardware import PIMHierarchy


def abstract_like(tree):
    """ShapeDtypeStruct stand-ins for a pytree of arrays — the 'trace
    without allocating' idiom used throughout the mapper."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


_abstract = abstract_like


def map_arch(name: str, kind: str = "train", *, seq_len: int = 128,
             batch: int = 1, smoke: bool = False,
             hierarchy: PIMHierarchy | None = None,
             policy: placement_mod.PlacementPolicy | None = None,
             tech: str = "proposed",
             weight_dtype: str = "fp32",
             act_dtype: str = "fp32",
             ideal_provision: str = "fp32",
             partitions: int | None = None,
             expand_scans: bool = False,
             expand_budget: int | None = None) -> schedule_mod.Schedule:
    """Map one registered architecture's train / serve step.

    ``kind='train'`` schedules a full optimizer step (fwd + bwd + update);
    ``kind='serve'`` schedules one decode step against a ``seq_len`` cache.
    ``smoke=True`` uses the reduced config (fast CI path).
    ``partitions=K`` cuts the step into K pipeline partitions (see
    ``Schedule.pipeline`` / ``compile_partitioned``);
    ``expand_scans=True`` first expands the scanned layer stack into
    resident per-layer copies (capacity-bucketed against
    ``expand_budget`` subarrays) so cuts can land inside it.
    ``weight_dtype`` stores weights on a reduced-precision grid
    (``"int8"`` / ``"fp8_e4m3"`` / ``"fp8_e5m2"`` / ``"fp16"``) and
    spends the freed subarrays on replicas (see
    ``build_schedule``); ``ideal_provision="quantized"`` provisions the
    ideal-latency reference at the reduced grid's density instead of
    fp32-equivalent area.
    """
    from repro.launch import steps as steps_mod

    cfg = (configs.get_smoke_config(name) if smoke
           else configs.get_config(name))
    if kind == "train" and cfg.grad_accum > 1:
        # train steps scan grad_accum microbatches; keep batch divisible
        batch = max(1, -(-batch // cfg.grad_accum)) * cfg.grad_accum
    shape = ShapeSpec(f"map_{kind}", seq_len, batch, kind)
    p_shapes = steps_mod.abstract_params(cfg)
    if kind == "train":
        step = steps_mod.make_train_step(cfg)
        o_shapes = steps_mod.abstract_opt_state(cfg, p_shapes)
        b_shapes = steps_mod.input_specs(cfg, shape)
        return schedule_mod.build_schedule(
            step, p_shapes, o_shapes, b_shapes,
            hierarchy=hierarchy, policy=policy, tech=tech,
            weight_dtype=weight_dtype, act_dtype=act_dtype,
            ideal_provision=ideal_provision,
            partitions=partitions, expand_scans=expand_scans,
            expand_budget=expand_budget)
    if kind == "serve":
        step = steps_mod.make_serve_step(cfg)
        c_shapes = steps_mod.abstract_cache(cfg, shape)
        token, pos = steps_mod.decode_input_specs(cfg, shape)
        return schedule_mod.build_schedule(
            step, p_shapes, c_shapes, token, pos,
            hierarchy=hierarchy, policy=policy, tech=tech,
            weight_dtype=weight_dtype, act_dtype=act_dtype,
            ideal_provision=ideal_provision,
            partitions=partitions, expand_scans=expand_scans,
            expand_budget=expand_budget)
    raise ValueError(f"kind must be 'train' or 'serve', got {kind!r}")


def map_lenet(kind: str = "serve", *, batch: int = 4, lr: float = 0.05,
              hierarchy: PIMHierarchy | None = None,
              policy: placement_mod.PlacementPolicy | None = None,
              tech: str = "proposed",
              weight_dtype: str = "fp32",
              act_dtype: str = "fp32",
              ideal_provision: str = "fp32",
              partitions: int | None = None,
              expand_scans: bool = False) -> schedule_mod.Schedule:
    """Map the paper's LeNet: ``serve`` = forward pass, ``train`` = one
    SGD step on the cross-entropy loss. ``expand_scans`` is accepted for
    parity with :func:`map_arch` (LeNet lowers scan-free, so expansion
    is a no-op)."""
    from repro.configs.lenet5 import CONFIG
    from repro.models import lenet

    params = lenet.init_lenet(jax.random.PRNGKey(0), CONFIG)
    images = jax.ShapeDtypeStruct((batch, CONFIG.in_hw, CONFIG.in_hw, 1),
                                  jnp.float32)
    if kind == "serve":
        return schedule_mod.build_schedule(
            lenet.lenet_apply, _abstract(params), images,
            hierarchy=hierarchy, policy=policy, tech=tech,
            weight_dtype=weight_dtype, act_dtype=act_dtype,
            ideal_provision=ideal_provision,
            partitions=partitions, expand_scans=expand_scans)
    if kind == "train":
        labels = jax.ShapeDtypeStruct((batch,), jnp.int32)

        def train_step(params, images, labels):
            loss, grads = jax.value_and_grad(lenet.lenet_loss)(
                params, images, labels)
            new = jax.tree.map(lambda p, g: p - lr * g, params, grads)
            return new, loss

        return schedule_mod.build_schedule(
            train_step, _abstract(params), images, labels,
            hierarchy=hierarchy, policy=policy, tech=tech,
            weight_dtype=weight_dtype, act_dtype=act_dtype,
            ideal_provision=ideal_provision,
            partitions=partitions, expand_scans=expand_scans)
    raise ValueError(f"kind must be 'train' or 'serve', got {kind!r}")


def compile_arch(name: str, kind: str = "train", *, seq_len: int = 128,
                 batch: int = 1, smoke: bool = False,
                 hierarchy: PIMHierarchy | None = None,
                 policy: placement_mod.PlacementPolicy | None = None,
                 tech: str = "proposed", weight_dtype: str = "fp32",
                 act_dtype: str = "fp32",
                 block: int = 128,
                 interpret: bool | None = None,
                 partitions: int | None = None,
                 expand_scans: bool = False, devices=None):
    """Map one architecture's step and compile it to a jittable program
    (a ``PartitionedProgram`` of K stage programs when ``partitions=K``;
    ``devices`` pins each stage program to its own JAX device for the
    async pipeline driver)."""
    sched = map_arch(name, kind, seq_len=seq_len, batch=batch, smoke=smoke,
                     hierarchy=hierarchy, policy=policy, tech=tech,
                     weight_dtype=weight_dtype, act_dtype=act_dtype,
                     partitions=partitions, expand_scans=expand_scans)
    if partitions:
        return compile_mod.compile_partitioned(sched, block=block,
                                               interpret=interpret,
                                               devices=devices)
    return compile_mod.compile_schedule(sched, block=block,
                                        interpret=interpret)


def compile_lenet(kind: str = "serve", *, batch: int = 4, lr: float = 0.05,
                  hierarchy: PIMHierarchy | None = None,
                  policy: placement_mod.PlacementPolicy | None = None,
                  tech: str = "proposed", weight_dtype: str = "fp32",
                  act_dtype: str = "fp32",
                  block: int = 128,
                  interpret: bool | None = None,
                  partitions: int | None = None,
                  devices=None):
    """Map the paper's LeNet and compile it to a jittable program
    (a ``PartitionedProgram`` of K stage programs when ``partitions=K``;
    ``devices`` pins stages for the async pipeline driver)."""
    sched = map_lenet(kind, batch=batch, lr=lr, hierarchy=hierarchy,
                      policy=policy, tech=tech, weight_dtype=weight_dtype,
                      act_dtype=act_dtype, partitions=partitions)
    if partitions:
        return compile_mod.compile_partitioned(sched, block=block,
                                               interpret=interpret,
                                               devices=devices)
    return compile_mod.compile_schedule(sched, block=block,
                                        interpret=interpret)
