"""The system under test, driven as its launcher drives it.

``Program`` builds a cell's trainer step from the program's own entry
points, as ``launch/train.run_mesh`` builds it:

* one chip: ``distributed.trainer.make_train_step`` under
  ``jax.jit(..., donate_argnums=(0,))`` over M simulated workers, what
  ``run_mesh`` builds for ``--workers M``;
* C > 1 chips: ``trainer.jit_train_step`` on the (C, 1) ``(data, model)``
  mesh of the cell's own chips, one worker per chip on ``data``, with the
  flat state ZeRO'd over the traffic's ``state_fsdp_axes``: what
  ``run_mesh`` builds without ``--workers`` on a host of C chips.

The state comes from the seed in one jitted call on the device, through the
program's ``init_train_state``, laid out as the compiled step takes it.
Batches go through ``worker_split``.

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
        from repro.core.rules import CommRule
        from repro.distributed import trainer as T

        tr = cell.traffic
        self.cell = cell
        self.cfg = program_config(cell.config)
        self.vocab = self.cfg.vocab
        rule = CommRule(kind=tr["rule"]["kind"], c=tr["rule"]["c"],
                        d_max=tr["rule"]["d_max"],
                        max_delay=tr["rule"]["max_delay"])
        self.m = cell.workers
        if cell.chips == 1:
            self._one_chip(T, rule, devices[0])
        else:
            self._mesh(T, rule, devices)
        b1 = self.hp.b1
        layout = T.flat_layout(self.cfg, self.shards)
        f32 = [jnp.float32] * len(layout.dtypes)
        self._grad_norms = jax.jit(
            lambda h: leaf_norms(layout.unpack(h.astype(jnp.float32), f32))
            / (1.0 - b1))
        from repro.models.model import init_params
        # θ^0 comes out of its own call, stored in the parameters' dtype as
        # the state holds it: fused into the difference, the compiler may
        # keep the normal draws in float32 and skip that rounding.
        self._init_params = jax.jit(partial(init_params, self.cfg),
                                    out_shardings=self.params_sharding)
        self.compiled = None

    def _one_chip(self, T, rule, device):
        """M simulated workers on one device, an unsharded flat plane."""
        from jax.sharding import SingleDeviceSharding

        self.hp = T.TrainHParams(rule=rule, lr=float(self.cell.traffic["lr"]))
        self.shards = 1
        one = SingleDeviceSharding(device)
        self.state_sharding = self.batch_sharding = self.params_sharding = one
        self.jitted = jax.jit(T.make_train_step(self.cfg, self.hp, self.m),
                              donate_argnums=(0,))
        self.init = jax.jit(partial(T.init_train_state, self.cfg, self.hp,
                                    self.m), out_shardings=one)

    def _mesh(self, T, rule, devices):
        """One worker per chip on the ``data`` axis of the (C, 1) mesh over
        the cell's chips; the state and batch laid out as
        ``jit_train_step`` compiles for (``place_train_state``'s
        shardings)."""
        from jax.sharding import AxisType

        from repro.distributed.sharding import to_named
        from repro.launch.mesh import DATA, MODEL

        cell, chips = self.cell, self.cell.chips
        if self.m != chips:
            raise ValueError(
                f"{cell.name}: {self.m} workers on {chips} chips; the "
                f"harness runs one worker per chip")
        mesh = jax.make_mesh((chips, 1), (DATA, MODEL),
                             axis_types=(AxisType.Auto,) * 2,
                             devices=list(devices)[:chips])
        self.hp = T.TrainHParams(
            rule=rule, lr=float(cell.traffic["lr"]),
            state_fsdp_axes=tuple(cell.traffic["state_fsdp_axes"]))
        make, sspecs, m = T.jit_train_step(self.cfg, mesh, self.hp)
        self.shards = T.flat_state_shards(self.cfg, mesh, self.hp)
        self.state_sharding = to_named(mesh, sspecs)
        self.params_sharding = self.state_sharding.params
        rows = cell.global_batch
        tokens = jax.ShapeDtypeStruct((rows, cell.seq + 1), jnp.int32)
        batch_sds = T.worker_split_abstract({"tokens": tokens}, m)
        spec_for = T.train_batch_specs(mesh)
        self.batch_sharding = {k: to_named(mesh, spec_for(k, v.ndim))
                               for k, v in batch_sds.items()}
        self.jitted = make(batch_sds)
        self.init = jax.jit(
            partial(T.init_train_state, self.cfg, self.hp, m,
                    shards=self.shards),
            out_shardings=self.state_sharding)

    def batch(self, seed: int, step: int):
        from repro.distributed.trainer import worker_split
        toks = feed.step_tokens(self.cell.traffic, self.vocab, seed, step)
        return jax.device_put(worker_split({"tokens": toks}, self.m),
                              self.batch_sharding)

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
