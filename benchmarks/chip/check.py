"""The correctness comparison: the system's first rounds against the plain
reference (``reference/``) on the same seed, weights recipe and inputs.

The system's readings come from ``Session.warm_up``: the worker-mean loss
of every local step of the first three rounds, the masks after each
round, the norm of each momentum leaf after round 1 (the optimizer's
accumulated gradient), and the norm of what the three rounds changed in
each leaf of theta, of every consensus level z and of every dual v.  The
reference makes the same readings.  A leaf's gap is |norm - ref norm| /
max(ref norm, median ref norm of its family).

``loss_gap``          max |loss - ref| / |ref| over the 3 x E losses
``loss_mean_gap``     the mean of the same over the 3 x E losses
``first_loss_gap``    the same for the first local step alone
``mom_gap``           the worst momentum leaf's gap
``theta_change_gap``  the worst theta leaf's gap
``z_change_gap``      the worst leaf's gap over every z level
``z_median_gap``      the median leaf's gap over every z level
``v_change_gap``      the worst leaf's gap over every dual v
``v_median_gap``      the median leaf's gap over every dual v
``mask_flips``        groups kept on one side and dropped on the other

A cell compares the numbers its ``limits`` name; the others go to
standard error for diagnosis.  A leaf whose reference momentum is under
a thousandth of the median leaf's has no gradient to speak of and is left
out of every leaf number (none of the benchmark's configurations has
one).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .reference import hsadmm as ref_h
from .reference import resnet as ref_r

NUMBERS = ("loss_gap", "loss_mean_gap", "first_loss_gap", "mom_gap", "theta_change_gap",
           "z_change_gap", "z_median_gap", "v_change_gap", "v_median_gap", "mask_flips")
ROUNDS = 3


def reference_arch(cell: dict) -> dict:
    arch = dict(cell["config"]["arch"])
    if cell["traffic"]["phase"] == "reconfigured":
        arch = ref_h.reconfigured_arch(
            arch, cell["config"]["hsadmm"]["keep_rate"])
    return arch


def reference_readings(cell: dict, pool, key, *, dtype=jnp.float32,
                       compute_dtype=None, half_batch: bool = False,
                       drop_top_exchange: bool = False,
                       mask_fault: str | None = None,
                       device=None) -> dict:
    """The reference's readings of the cell's first ``ROUNDS`` rounds.

    ``dtype`` bfloat16 (parameters held in bfloat16) is the control, and
    ``compute_dtype`` bfloat16 (the local step computed in bfloat16 over
    float32 parameters) a second one; ``half_batch``,
    ``drop_top_exchange`` and ``mask_fault`` plant the faults the
    calibration reads."""
    arch = reference_arch(cell)
    hp = ref_h.Hyper.from_config(cell["config"], cell["traffic"])
    frozen = cell["traffic"]["phase"] == "reconfigured"
    classes = tuple(ref_h.prune_classes(arch, hp.keep_rate))
    device = device or jax.devices()[0]
    with jax.default_matmul_precision("highest"), \
            jax.default_device(device):
        p0 = jax.jit(lambda k: ref_h.flatten(
            ref_r.init(arch, k, jnp.float32)))(key)
        p0 = {k: v.astype(dtype) for k, v in p0.items()}
        state = ref_h.init_state(p0, hp, classes)
        eta = jnp.float32(cell["config"]["eta"])
        rec = {"losses": [], "masks": []}
        for r in range(ROUNDS):
            images, labels = pool.round_inputs(r, hp.local_steps)
            state, losses = ref_h.round_(
                state, arch, hp, classes, jnp.asarray(images, dtype),
                jnp.asarray(labels), eta, frozen=frozen,
                half_batch=half_batch, drop_top_exchange=drop_top_exchange,
                mask_fault=mask_fault, compute_dtype=compute_dtype)
            rec["losses"].append(np.asarray(losses, np.float32))
            rec["masks"].append({k: np.asarray(v)
                                 for k, v in state["masks"].items()})
            if r == 0:
                rec["mom"] = _stack_norms(state["mom"])
        rec["losses"] = np.stack(rec["losses"])
        change = {"theta": _stack_norms(
            [_sub(t, p0) for t in state["theta"]])}
        for lvl, zs in enumerate(state["z"]):
            change[f"z{lvl + 1}"] = _stack_norms([_sub(z, p0) for z in zs])
        for lvl, vs in enumerate(state["v"]):
            change[f"v{lvl + 1}"] = _stack_norms(vs)
        rec["change"] = change
    return rec


def _sub(a: dict, b: dict) -> dict:
    return {k: a[k].astype(jnp.float32) - b[k].astype(jnp.float32)
            for k in a}


def _stack_norms(trees: list) -> dict:
    """{leaf: norm over every tree of the list taken as one stack}."""
    return {k: float(math.sqrt(sum(float(jnp.sum(jnp.square(
        t[k].astype(jnp.float32)))) for t in trees))) for k in trees[0]}


def _leaf_gaps(got: dict, ref: dict, keep: set) -> list:
    """Each kept leaf's |norm - ref norm| / max(ref norm, median ref norm)."""
    keys = sorted(k for k in ref if k in keep)
    missing = [k for k in keys if k not in got]
    if missing:
        raise KeyError(f"system readings lack leaves {missing[:3]}")
    med = float(np.median([ref[k] for k in keys]))
    return [abs(float(got[k]) - ref[k]) / max(ref[k], med, 1e-30)
            for k in keys]


def mask_flips(got: dict, ref: dict) -> int:
    """Groups whose kept/dropped state differs between the two sides,
    summed over classes and rounds."""
    return int(sum(np.sum(np.asarray(g[c]) != np.asarray(r[c]))
                   for g, r in zip(got["masks"], ref["masks"]) for c in r))


def compare(got: dict, ref: dict) -> dict:
    """The comparison's numbers, from the system's readings ``got`` and
    the reference's ``ref`` (both as ``Session.warm_up`` returns them)."""
    lr = np.asarray(ref["losses"], np.float64)
    lg = np.asarray(got["losses"], np.float64).reshape(lr.shape)
    med_mom = float(np.median(list(ref["mom"].values())))
    keep = {k for k, v in ref["mom"].items() if v >= 1e-3 * med_mom}
    fams = ref["change"]

    def family(first):
        return [g for f in fams if f[0] == first
                for g in _leaf_gaps(got["change"][f], fams[f], keep)]
    z_gaps, v_gaps = family("z"), family("v")
    rel = np.abs(lg - lr) / np.abs(lr)
    out = {"loss_gap": float(np.max(rel)),
           "loss_mean_gap": float(np.mean(rel)),
           "first_loss_gap": float(rel.flat[0]),
           "mom_gap": max(_leaf_gaps(got["mom"], ref["mom"], keep)),
           "theta_change_gap": max(_leaf_gaps(got["change"]["theta"],
                                              fams["theta"], keep)),
           "z_change_gap": max(z_gaps),
           "z_median_gap": float(np.median(z_gaps))}
    if v_gaps:
        out["v_change_gap"] = max(v_gaps)
        out["v_median_gap"] = float(np.median(v_gaps))
    out["mask_flips"] = mask_flips(got, ref)
    if not np.all(np.isfinite(lg)):
        out = {k: float("inf") for k in out}
    return out


def verdict(numbers: dict, limits: dict) -> bool:
    """Correct when every number the cell has a limit for is inside it."""
    return all(k in numbers and numbers[k] <= lim
               for k, lim in limits.items())
