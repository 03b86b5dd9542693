"""Numerical executor: run a static schedule with the real Pallas kernels.

Interprets the schedule's jaxpr equation by equation through the shared
lowering-rule table (``repro.mapper.lowering``). Placed matmul nodes
execute as one ``pim_matmul`` call *per placed weight block* (partial
products accumulated across k-blocks — the block structure of the placement
drives the compute, so the schedule is real, not just an abacus); simple
convolutions lower to im2col + the same placed blocked matmul; eltwise
add/sub/mul run through ``pim_mac``. Everything else (transposes,
reshapes, nonlinearities, control flow) falls back to the primitive's bind,
so any traceable fn executes and the output must match ``jax.jit(fn)`` to
fp32 tolerance.

This eager per-equation, per-block walk is the **debugging/verification
mode** — and the *per-block oracle* the compiled path
(``repro.mapper.compile``) must match: the compiler evaluates the identical
rule table but with ``group=True``/``fuse=True``, stacking each node's
blocks into one ``pim_matmul_grouped`` launch. Grouped execution is
constructed to be bit-identical to this oracle (same per-block tile
shapes, same fold order — see ``repro.mapper.lowering``), so
``tests/test_grouped.py`` asserts exact equality, not tolerance.

``placed_blocks`` / ``eltwise_calls`` count the kernel-routed work and
``kernel_launches`` the pallas dispatches, so tests can assert the PIM
path actually ran (here launches == blocks + eltwise by construction).
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np

from repro import obs
from repro.mapper.lowering import LoweringContext, eval_placed
from repro.mapper.schedule import Schedule


@dataclasses.dataclass
class ScheduleExecutor:
    """Run ``schedule`` numerically; see module docstring.

    ``group``/``fuse`` default to False — the executor is the per-block
    oracle. Flip them to interpret eagerly through the grouped kernels
    (mostly useful for debugging the grouped path itself).
    """

    schedule: Schedule
    interpret: bool | None = None   # None: compiled on a TPU only
    block: int = 128              # pallas tile edge (pad-to multiple)
    group: bool = False
    fuse: bool = False

    def __post_init__(self):
        self._ctx = LoweringContext(self.schedule, block=self.block,
                                    interpret=self.interpret,
                                    group=self.group, fuse=self.fuse)

    # kernel-routed work/dispatch counters live on the shared lowering ctx
    @property
    def placed_blocks(self) -> int:
        return self._ctx.placed_blocks

    @property
    def eltwise_calls(self) -> int:
        return self._ctx.eltwise_calls

    @property
    def kernel_launches(self) -> int:
        return self._ctx.kernel_launches

    @property
    def matmul_launches(self) -> int:
        return self._ctx.matmul_launches

    @property
    def eltwise_launches(self) -> int:
        return self._ctx.eltwise_launches

    # -- public API ---------------------------------------------------------

    def run(self, *args, **kwargs):
        closed = self.schedule.graph.closed_jaxpr
        flat, in_tree = jax.tree.flatten((args, kwargs))
        if (self.schedule.graph.in_tree is not None
                and in_tree != self.schedule.graph.in_tree):
            raise TypeError(
                f"argument structure {in_tree} != traced structure "
                f"{self.schedule.graph.in_tree}")
        tr = obs.tracer()
        if tr.enabled:
            # depth-0 run span: drift takes this as measured_total; the
            # per-node launch spans recorded inside eval_eqns nest under it
            with tr.span("run:schedule", lane="execute",
                         group=self.group, fuse=self.fuse):
                outs = eval_placed(self._ctx, closed.jaxpr, closed.consts,
                                   flat)
                jax.block_until_ready(outs)
        else:
            outs = eval_placed(self._ctx, closed.jaxpr, closed.consts, flat)
        m = obs.metrics()
        m.counter("executor.runs").inc()
        m.gauge("executor.placed_blocks").set(self._ctx.placed_blocks)
        m.gauge("executor.kernel_launches").set(self._ctx.kernel_launches)
        out_tree = self.schedule.graph.out_tree
        return jax.tree.unflatten(out_tree, outs) if out_tree else outs

    def verify(self, *args, rtol: float = 1e-4, atol: float = 1e-4,
               **kwargs) -> float:
        """Run the schedule and compare against ``jax.jit(fn)``. Returns the
        max abs deviation; raises if outside fp32 tolerance."""
        fn = self.schedule.graph.fn
        assert fn is not None, "graph was built without a fn reference"
        got = self.run(*args, **kwargs)
        want = jax.jit(fn)(*args, **kwargs)
        worst = 0.0
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            g, w = np.asarray(g), np.asarray(w)
            np.testing.assert_allclose(g, w, rtol=rtol, atol=atol)
            if g.size:
                worst = max(worst, float(np.max(np.abs(g - w))))
        return worst


def run_schedule(schedule: Schedule, *args, interpret: bool | None = None,
                 **kwargs):
    """One-shot: execute ``schedule`` on concrete inputs."""
    return ScheduleExecutor(schedule, interpret=interpret).run(*args, **kwargs)
