"""The system under test, driven as its launcher drives it.

``Program`` builds the trainer step of a one-chip cell from the program's
own entry point: ``distributed.trainer.make_train_step`` under
``jax.jit(..., donate_argnums=(0,))`` over M simulated workers, what
``launch/train.run_mesh`` builds for ``--workers M``. The state comes from
the seed in one jitted call on the device, through the program's
``init_train_state``. Batches go through ``worker_split``.

This module imports the program; the reference (``refstep``,
``references/``) does not.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import feed
from bench.refstep import (UPDATE_STEPS, Readings, diff_leaf_norms,
                           leaf_names, leaf_norms, seed_key)


def program_config(config: dict):
    from repro.models.config import ModelConfig
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {k: v for k, v in config.items() if k in fields}
    kw["reduced"] = tuple(tuple(r) for r in kw.get("reduced", ()))
    return ModelConfig(**kw)


GATE_KEYS = ("upload_mask", "lhs", "rhs", "dtheta_sq")


@dataclasses.dataclass
class Window:
    """What the measured window leaves: its start, every step's completion
    time, per step its loss and the gate's readings (``GATE_KEYS``, those
    the rule reports), and per completion the time the loop's pass that
    saw it spent preparing a batch, dispatching and waiting."""
    t0: float
    done: list
    losses: list
    gate: dict
    phases: list


class Program:
    """One cell's compiled step, its state and its feed."""

    def __init__(self, cell, devices):
        from jax.sharding import SingleDeviceSharding

        from repro.core.rules import CommRule
        from repro.distributed import trainer as T

        if cell.chips != 1:
            raise ValueError(f"{cell.name}: the harness drives one chip; "
                             f"the cell asks for {cell.chips}")
        tr = cell.traffic
        self.cell = cell
        self.cfg = program_config(cell.config)
        self.vocab = self.cfg.vocab
        rule = tr["rule"]
        self.hp = T.TrainHParams(
            rule=CommRule(kind=rule["kind"], c=rule["c"],
                          d_max=rule["d_max"], max_delay=rule["max_delay"]),
            lr=float(tr["lr"]))
        self.m = cell.workers
        self.shards = 1
        self.sharding = SingleDeviceSharding(devices[0])
        self.jitted = jax.jit(T.make_train_step(self.cfg, self.hp, self.m),
                              donate_argnums=(0,))
        self.init = jax.jit(partial(T.init_train_state, self.cfg, self.hp,
                                    self.m), out_shardings=self.sharding)
        layout = T.flat_layout(self.cfg)
        f32 = [jnp.float32] * len(layout.dtypes)
        b1 = self.hp.b1
        self._grad_norms = jax.jit(
            lambda h: leaf_norms(layout.unpack(h.astype(jnp.float32), f32))
            / (1.0 - b1))
        from repro.models.model import init_params
        # θ^0 comes out of its own call, stored in the parameters' dtype as
        # the state holds it: fused into the difference, the compiler may
        # keep the normal draws in float32 and skip that rounding.
        self._init_params = jax.jit(partial(init_params, self.cfg),
                                    out_shardings=self.sharding)
        self.compiled = None

    def batch(self, seed: int, step: int):
        from repro.distributed.trainer import worker_split
        toks = feed.step_tokens(self.cell.traffic, self.vocab, seed, step)
        return jax.device_put(worker_split({"tokens": toks}, self.m),
                              self.sharding)

    def new_state(self, seed: int):
        return self.init(seed_key(seed))

    def compile(self, state, batch):
        """AOT-compile the step for this state and batch (the persistent
        cache serves it after a checkout's first run)."""
        self.compiled = self.jitted.lower(state, batch).compile()
        return self.compiled

    def memory_analysis(self):
        return self.compiled.memory_analysis()

    def first_steps(self, state, seed: int, steps: int):
        """Drive the state through its first ``steps`` steps with the
        window's own call and feed, reading what the comparison needs:
        each step's loss, upload mask, ‖θ^{k+1} − θ^k‖² and the rule's LHS
        and RHS, the first aggregate from h after step 1, and θ^3 − θ^0
        before step 4 can take θ^3."""
        losses, lhs, rhs, masks, dsq = [], [], [], [], []
        grad = upd = None
        for k in range(steps):
            state, mets = self.compiled(state, self.batch(seed, k))
            losses.append(float(mets["loss"]))
            masks.append(np.asarray(mets["upload_mask"]).tolist())
            dsq.append(float(mets["dtheta_sq"]))
            if "lhs" in mets:
                lhs.append(np.asarray(mets["lhs"]).tolist())
                rhs.append(float(mets["rhs"]))
            if k == 0:
                grad = np.asarray(self._grad_norms(state.h))
            if k == UPDATE_STEPS - 1:
                theta0 = self._init_params(seed_key(seed))
                upd = np.asarray(diff_leaf_norms(state.params, theta0))
                del theta0
        names = leaf_names(state.params)
        return state, Readings(
            losses=losses, grad_norms=dict(zip(names, grad)),
            update_norms=dict(zip(names, upd)), lhs=lhs or None,
            rhs=rhs or None, masks=masks, dtheta_sq=dsq)

    def window(self, state, seed: int, first_step: int, seconds: float,
               annotate):
        """Measure for ``seconds``: dispatch step i+1, then wait for step
        i's loss, so one step is always in flight. The garbage collector
        is held off inside the window (objects made before it are frozen
        out of later collections), so no collection lands in a step."""
        done, losses, phases = [], [], []
        gate = {}
        k = first_step
        pending = None
        gc.collect()
        gc.freeze()
        gc.disable()
        try:
            t0 = time.perf_counter()
            with annotate("bench.window"):
                while True:
                    t_a = time.perf_counter()
                    with annotate("bench.prep"):
                        batch = self.batch(seed, k)
                    t_b = time.perf_counter()
                    with annotate("bench.dispatch"):
                        state, mets = self.compiled(state, batch)
                    t_c = time.perf_counter()
                    if pending is not None:
                        with annotate("bench.wait"):
                            pending.block_until_ready()
                        done.append(time.perf_counter())
                        phases.append((t_b - t_a, t_c - t_b, done[-1] - t_c))
                    pending = mets["loss"]
                    losses.append(pending)
                    for key in GATE_KEYS:
                        if key in mets:
                            gate.setdefault(key, []).append(mets[key])
                    del mets, batch
                    k += 1
                    if time.perf_counter() - t0 >= seconds:
                        break
                t_c = time.perf_counter()
                with annotate("bench.wait"):
                    pending.block_until_ready()
                done.append(time.perf_counter())
                phases.append((0.0, 0.0, done[-1] - t_c))
        finally:
            gc.enable()
            gc.unfreeze()
        return state, Window(t0=t0, done=done, losses=losses, gate=gate,
                             phases=phases)
