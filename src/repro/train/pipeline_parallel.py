"""Pipeline parallelism: the paper's Algorithm 2 re-expressed on the device
mesh (DESIGN §3 mapping).

The layer stack is partitioned into n stages (the execution trees of the
device dataflow — coarse level); the batch is split into m microbatches (the
horizontal splits — medium level); each microbatch rides through the stages
like a shared cache through activity threads, with `collective_permute`
playing the pipeline hand-off.  The GPipe makespan

    T_p(m) = (m + n - 1) * t_stage + overheads  ~=  c/m + (m-1) t_j + n t0

is the paper's §4.2 cost model with t_j = the staggering (slowest) stage, so
Theorem 1's m* = sqrt((c - lambda N)/t0) chooses the microbatch count — the
same closed form, with t0 = per-microbatch fixed overhead (dispatch +
permute latency).

`gpipe_spmd` builds the schedule inside one shard_map: every device holds
one stage's parameters (P('stage') sharding), steps t = 0..m+n-2 run
lock-step SPMD, and activations rotate stage i -> i+1 between steps.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.planner import theorem1_m_star


def plan_microbatches(total_net_time: float, n_stages: int, t0: float,
                      m_max: int = 64) -> int:
    """Theorem-1 microbatch count for a pipeline of ``n_stages`` whose total
    per-batch net compute is ``total_net_time`` and per-microbatch fixed
    overhead is ``t0``.  In the paper's terms the staggering activity is the
    slowest stage: with even stages lambda*N = total/n per microbatch."""
    c = total_net_time
    lam_N = total_net_time / max(n_stages, 1)
    m = theorem1_m_star(c, 1.0, lam_N, t0, m_max=m_max)
    return max(1, min(int(round(m)), m_max))


def gpipe_spmd(stage_fn: Callable[[Any, jax.Array], jax.Array],
               mesh, n_stages: int, m: int, axis: str = "stage"):
    """Returns pipelined(stacked_params, xs) with
    stacked_params: [n_stages, ...] pytree (stage-sharded),
    xs: [m, mb, ...] microbatched input (replicated),
    -> ys: [m, mb, ...] outputs of the last stage (replicated).
    """

    def inner(params, xs):
        # shard_map gives each device params[1, ...]; drop the stage dim
        params = jax.tree.map(lambda a: a[0], params)
        sid = jax.lax.axis_index(axis)
        n_steps = m + n_stages - 1
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        h0 = jnp.zeros_like(xs[0])
        outs0 = jnp.zeros_like(xs)

        def step(carry, t):
            h_recv, outs = carry
            # stage 0 ingests microbatch t while t < m; later stages use the
            # activation received from the previous stage (Algorithm 2: a
            # consumer thread hands its shared cache to the next activity)
            x_t = xs[jnp.minimum(t, m - 1)]
            h_in = jnp.where(sid == 0, x_t, h_recv)
            h_out = stage_fn(params, h_in)
            # last stage emits microbatch (t - n_stages + 1) when valid
            out_idx = jnp.clip(t - (n_stages - 1), 0, m - 1)
            valid = (sid == n_stages - 1) & (t >= n_stages - 1)
            upd = jnp.where(valid, h_out, outs[out_idx])
            outs = jax.lax.dynamic_update_index_in_dim(outs, upd, out_idx, 0)
            # rotate activations stage i -> i+1 (pipeline hand-off)
            h_next = jax.lax.ppermute(h_out, axis, perm)
            return (h_next, outs), None

        (h_last, outs), _ = jax.lax.scan(step, (h0, outs0),
                                         jnp.arange(n_steps))
        # broadcast the last stage's output buffer to all stages
        outs = jax.lax.psum(
            jnp.where(sid == n_stages - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    pspec = jax.tree.map(lambda _: P(axis), jax.tree.structure((0,)))
    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False)


def stack_stage_params(param_list) -> Any:
    """[per-stage pytree, ...] -> one pytree with leading n_stages dim."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *param_list)
