"""The plain reference of the trainer's first steps: M workers' gradients,
the communication rule, the eq. (3) aggregate and AMSGrad, written out in
float32 ``jax.numpy`` from the paper (Chen et al., CADA, eqs. 2a-2c, 3, 10)
with nothing taken from the program.

The rule ``cada2``: worker m evaluates its own sample at θ^k and again at
the iterate θ^{k-τ_m} it last uploaded at; it uploads when
||∇(θ^k) − ∇(θ^{k-τ_m})||² exceeds (c / d_max) · Σ of the last d_max
||θ^{j+1} − θ^j||², or when τ_m has reached max_delay (so every worker
uploads at k = 0). ``always`` uploads every gradient: distributed AMSGrad.
The server adds the mean of the uploaded innovations to ∇̄ (eq. 3) and
takes an AMSGrad step with ε inside the square root; θ is stored in the
configuration's dtype, rounded after each step, as the configuration says.

``fault`` plants a fault in this reference put in the program's place, for
reading what the comparison makes of it: ``"state_unchanged"`` returns
θ, h and v̂ as they came, every step; ``"half_batch"`` leaves out the
second half of every worker's rows and takes the mean over the rest;
``"gate_always"`` is a gate that never skips (every worker uploads every
step); ``"rhs_scaled"`` takes the RHS ten times too large;
``"ring_latest"`` evaluates the second gradient at θ^{k-1} whatever τ_m,
as a stale-iterate ring indexed wrongly for τ_m >= 2 would;
``"no_exchange"`` leaves out the exchange between chips of a step with one
worker per chip and the server state split over them: the leaves laid end
to end, padded to a multiple of 8 · M and cut into M equal blocks, block s
of the aggregate takes worker s's upload alone (over M).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import feed

UPDATE_STEPS = 3        # θ^3 − θ^0 is compared
FAULTS = ("state_unchanged", "half_batch", "gate_always", "rhs_scaled",
          "ring_latest", "no_exchange")


@dataclass
class Readings:
    """What the comparison reads of the first steps, program or
    reference."""
    losses: list            # per step, mean over workers
    grad_norms: dict        # leaf -> ||∇̄^0||, the first aggregate
    update_norms: dict      # leaf -> ||θ^3 − θ^0||
    lhs: list | None        # per step, per worker rule LHS (cada2)
    rhs: list | None        # per step, the rule's RHS (cada2)
    masks: list             # per step, per worker upload
    forced: list | None = None  # per step, per worker τ_m >= max_delay
    dtheta_sq: list | None = None  # per step, ||θ^{k+1} − θ^k||²


def leaf_names(tree) -> list:
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


@jax.jit
def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def diff_leaf_norms(a, b):
    return leaf_norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))


def seed_key(seed: int):
    """The PRNG key of a seed of any size, past 32 bits too."""
    seed = int(seed) % 2 ** 64
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def reference_run(model, cfg: dict, traffic: dict, seed: int, steps: int,
                  precision: str = "highest", fault: str | None = None,
                  follow: list | None = None) -> Readings:
    """``steps`` steps (at least ``UPDATE_STEPS``) of the plain reference
    from ``seed``. With ``follow`` (per step, per worker) the workers upload
    as it says rather than as the reference's own gate would decide, so
    that the reference stays on the path of the run it is compared with;
    the readings keep the reference's own LHS, RHS and forced uploads,
    against which each followed decision is judged."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no fault {fault!r}; known: {FAULTS}")
    rule = traffic["rule"]
    kind, m = rule["kind"], int(traffic["workers"])
    if kind not in ("cada2", "always"):
        raise ValueError(f"the reference has no rule {kind!r}")
    c, d_max, max_delay = rule["c"], int(rule["d_max"]), int(rule["max_delay"])
    lr = float(traffic["lr"])
    b1, b2, eps = 0.9, 0.999, 1e-8
    f32 = jnp.float32

    @jax.jit
    def grad(params, tokens):
        p32 = jax.tree.map(lambda a: a.astype(f32), params)
        return jax.value_and_grad(
            lambda p: model.loss(p, tokens, cfg, precision))(p32)

    @jax.jit
    def sq_diff(a, b):
        return sum(jnp.sum(jnp.square(x - y)) for x, y in
                   zip(jax.tree.leaves(a), jax.tree.leaves(b)))

    @jax.jit
    def add_scaled(acc, x, w):
        return jax.tree.map(lambda a, b: a + w * b, acc, x)

    @partial(jax.jit, static_argnums=3)
    def add_block(acc, x, w, s):
        """``acc + w · x`` on block ``s`` of ``no_exchange``'s M blocks."""
        leaves, tree = jax.tree.flatten(x)
        n = sum(b.size for b in leaves)
        block = (n + (-n) % (8 * m)) // m
        out, off = [], 0
        for a, b in zip(jax.tree.leaves(acc), leaves):
            pos = off + jnp.arange(b.size, dtype=jnp.int32).reshape(b.shape)
            out.append(a + jnp.where(pos // block == s, w * b, 0.0))
            off += b.size
        return jax.tree.unflatten(tree, out)

    def aggregate(acc, x, w, worker):
        if fault == "no_exchange":
            return add_block(acc, x, w, worker)
        return add_scaled(acc, x, w)

    @jax.jit
    def amsgrad(theta, h, v, g):
        h = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, h, g)
        v = jax.tree.map(lambda a, b: jnp.maximum(b2 * a + (1 - b2) * b * b,
                                                  a), v, g)
        upd = jax.tree.map(lambda a, b: -lr * a / jnp.sqrt(eps + b), h, v)
        theta = jax.tree.map(lambda t, u: (t.astype(f32) + u).astype(t.dtype),
                             theta, upd)
        return theta, h, v, sum(jnp.sum(u * u) for u in jax.tree.leaves(upd))

    theta = jax.jit(partial(model.init_weights, cfg))(seed_key(seed))
    names = leaf_names(theta)
    theta0 = theta
    zeros = jax.jit(lambda t: jax.tree.map(lambda a: jnp.zeros(a.shape, f32),
                                           t))
    h, v, nabla = zeros(theta), zeros(theta), zeros(theta)
    wg = [zeros(theta) for _ in range(m)] if kind == "cada2" else None
    point = [theta] * m                      # θ^{k-τ_m}
    tau = [max_delay] * m
    diff_hist = np.zeros(d_max)
    losses, lhs_all, rhs_all, masks, forced, dsq_all = [], [], [], [], [], []
    grad_norms = update = None
    previous = theta                         # θ^{k-1}

    for k in range(steps):
        toks = feed.step_tokens(traffic, cfg["vocab"], seed, k)
        rows = toks.reshape(m, -1, toks.shape[-1])
        if fault == "half_batch":
            rows = rows[:, : rows.shape[1] // 2]
        fresh, step_loss = [], 0.0
        for w in range(m):
            lw, gw = grad(theta, jnp.asarray(rows[w]))
            step_loss += float(lw) / m
            fresh.append(gw)
        losses.append(step_loss)
        if kind == "always":
            upload = [True] * m
            nabla = zeros(theta)
            for w, gw in enumerate(fresh):
                nabla = aggregate(nabla, gw, 1.0 / m, w)
            lhs_all = rhs_all = forced = None
        else:
            rhs = c / d_max * float(diff_hist.sum())
            if fault == "rhs_scaled":
                rhs *= 10.0
            stale = ([previous] * m if fault == "ring_latest" else point)
            lhs = [float(sq_diff(fresh[w], grad(stale[w],
                                                jnp.asarray(rows[w]))[1]))
                   for w in range(m)]
            forced.append([tau[w] >= max_delay for w in range(m)])
            upload = ([bool(u) for u in follow[k]] if follow is not None
                      else [lhs[w] > rhs or forced[-1][w]
                            or fault == "gate_always" for w in range(m)])
            for w in range(m):
                if upload[w]:
                    delta = add_scaled(fresh[w], wg[w], -1.0)
                    nabla = aggregate(nabla, delta, 1.0 / m, w)
                    wg[w] = add_scaled(wg[w], delta, 1.0)
                    point[w] = theta
                    tau[w] = 1
                else:
                    tau[w] += 1
            lhs_all.append(lhs)
            rhs_all.append(rhs)
        masks.append(upload)
        if k == 0:
            grad_norms = dict(zip(names, np.asarray(leaf_norms(nabla))))
        del fresh
        previous = theta
        if fault == "state_unchanged":
            dsq = 0.0
        else:
            theta, h, v, dsq = amsgrad(theta, h, v, nabla)
        diff_hist[k % d_max] = float(dsq)
        dsq_all.append(float(dsq))
        if k == UPDATE_STEPS - 1:
            update = dict(zip(names, np.asarray(diff_leaf_norms(theta,
                                                                theta0))))
    return Readings(losses=losses, grad_norms=grad_norms,
                    update_norms=update, lhs=lhs_all, rhs=rhs_all,
                    masks=masks, forced=forced, dtheta_sq=dsq_all)

