"""Plain H-SADMM rounds (PruneX, arXiv 2512.14628, Algorithm 1), written
from the paper for the ResNet family of ``reference.resnet``.

One round is E local proximal-SGD steps on every worker, then the
hierarchical consensus: the node-level candidate z~1 (Eq. 9), its
projection onto the structured-sparsity set by a global top-k of group
magnitudes (Eq. 10, the masks agreed by averaging the node scores), the
weighted means of the higher levels with their dropped groups held at
zero (Eq. 11, paper section 4.4), the dual updates (Eq. 12-13) and the
layer-wise adaptive penalties with scaled-dual rescaling (section 3.4).

Every quantity is a Python list over workers or consensus groups of flat
``{leaf name: array}`` dicts, every sum a Python sum: nothing is batched
or fused, and nothing imports the system under test.  The local step runs
one worker at a time, so the reference fits on the chip beside nothing
else once the system's state is freed.

The pruning classes are the channel sets the ResNet wiring couples
(PruneTrain's mask propagation): each stage's inner width; each residual
stream, which the stem or a projection shortcut opens and every block
output, block input and the classifier rows share; GroupNorm parameters
follow their channels without voting.  A class prunes whole GroupNorm
groups and keeps ``floor(keep_rate * groups)`` of them.

Faults can be planted for the benchmark's calibration: ``half_batch``
(each worker's gradient from the first half of its batch only),
``drop_top_exchange`` (the top level's mean taken over the first child
group alone, as if the exchange between nodes never happened) and
``mask_fault``: ``"reversed"`` keeps the lowest-scoring groups,
``"unsynced"`` ranks by the first node's scores alone, as if the mask
sync between nodes never happened.  ``compute_dtype`` bfloat16 runs the
local step (forward, backward, GroupNorm and the prox update) in
bfloat16 over float32 parameters and momenta: a lower compute precision
with float32 master weights.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from . import resnet


@dataclass(frozen=True)
class Hyper:
    levels: tuple
    local_steps: int
    keep_rate: float
    rho1: float = 1.5e-3
    rho2: float = 1.5e-4
    rho_max: float = 10.0
    adapt_mu: float = 10.0
    adapt_tau: float = 2.0
    weight_decay: float = 1e-4
    momentum: float = 0.9

    @classmethod
    def from_config(cls, cfg: dict, traffic: dict) -> "Hyper":
        h = cfg["hsadmm"]
        return cls(levels=tuple(traffic["levels"]),
                   local_steps=h["local_steps"], keep_rate=h["keep_rate"],
                   **{k: h[k] for k in ("rho1", "rho2", "rho_max",
                                        "adapt_mu", "adapt_tau",
                                        "weight_decay", "momentum")
                      if k in h})


# ---------------------------------------------------------------------------
# flat parameter dicts
# ---------------------------------------------------------------------------


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else k
        out.update(flatten(v, path) if isinstance(v, dict) else {path: v})
    return out


def unflatten(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split("/")
        for p in head:
            node = node.setdefault(p, {})
        node[last] = v
    return out


# ---------------------------------------------------------------------------
# pruning classes
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class PruneClass:
    name: str
    members: tuple      # ((leaf, axis), ...): vote on group magnitude
    followers: tuple    # ((leaf, axis), ...): masked, never vote
    groups: int
    keep: int
    group_size: int


def prune_classes(arch: dict, keep_rate: float) -> list[PruneClass]:
    """The channel sets a structured prune of ``arch`` has to cut together."""
    gs = arch["gn_size"]
    stem, outs, cmid = resnet.widths(arch)
    classes: list[dict] = []

    def new(name, width):
        classes.append({"name": name, "width": width, "m": [], "f": []})
        return classes[-1]

    stream = new("stem", stem)
    stream["m"].append(("stem", 3))
    stream["f"] += [("gn0/scale", 0), ("gn0/bias", 0)]
    mids = {}
    for si, bi, cin, cm, cout, s in resnet.block_shapes(arch):
        p = f"layer{si}/b{bi}"
        if si not in mids:
            mids[si] = new(f"mid{si}", cm)
        mid = mids[si]
        stream["m"].append((f"{p}/conv1", 2))
        mid["m"].append((f"{p}/conv1", 3))
        mid["f"] += [(f"{p}/gn1/scale", 0), (f"{p}/gn1/bias", 0)]
        if arch["bottleneck"]:
            mid["m"] += [(f"{p}/conv2", 2), (f"{p}/conv2", 3),
                         (f"{p}/conv3", 2)]
            mid["f"] += [(f"{p}/gn2/scale", 0), (f"{p}/gn2/bias", 0)]
            last, last_gn = f"{p}/conv3", f"{p}/gn3"
        else:
            mid["m"].append((f"{p}/conv2", 2))
            last, last_gn = f"{p}/conv2", f"{p}/gn2"
        if s != 1 or cin != cout:
            stream["m"].append((f"{p}/down", 2))
            stream = new(f"out{si}", cout)
            stream["m"].append((f"{p}/down", 3))
            stream["f"] += [(f"{p}/gnd/scale", 0), (f"{p}/gnd/bias", 0)]
        stream["m"].append((last, 3))
        stream["f"] += [(f"{last_gn}/scale", 0), (f"{last_gn}/bias", 0)]
    stream["m"].append(("fc_w", 0))
    out = []
    for c in classes:
        groups = c["width"] // gs
        if groups < 2:
            continue          # too narrow to prune: stays dense
        keep = min(groups, max(1, int(groups * keep_rate)))
        out.append(PruneClass(c["name"], tuple(c["m"]), tuple(c["f"]),
                              groups, keep, gs))
    return out


def reconfigured_arch(arch: dict, keep_rate: float) -> dict:
    """``arch`` with every prunable width cut to its kept groups: the model
    a physical reconfiguration trains once the masks have frozen."""
    gs = arch["gn_size"]

    def cut(w):
        groups = w // gs
        if groups < 2:
            return w
        return min(groups, max(1, int(groups * keep_rate))) * gs

    stem, outs, cmid = resnet.widths(arch)
    return dict(arch, stem=cut(stem), outs=[cut(w) for w in outs],
                cmid=[cut(w) for w in cmid])


def _along(v, axis, ndim):
    shape = [1] * ndim
    shape[axis] = v.shape[0]
    return v.reshape(shape)


def group_scores(z: dict, c: PruneClass):
    """Squared magnitude of each of the class's groups in one tree."""
    total = 0.0
    for key, axis in c.members:
        x = z[key].astype(jnp.float32)
        axes = tuple(i for i in range(x.ndim) if i != axis)
        total = total + jnp.sum(jnp.square(x), axis=axes)
    return jnp.sum(total.reshape(c.groups, c.group_size), axis=1)


def select_masks(nodes: list, classes: list, fault: str | None = None
                 ) -> dict:
    """Global masks: top-``keep`` groups of the node-averaged scores
    (``fault`` plants a wrong selection, see the module's docstring)."""
    out = {}
    for c in classes:
        voters = nodes[:1] if fault == "unsynced" else nodes
        score = sum(group_scores(z, c) for z in voters) / len(voters)
        if fault == "reversed":
            score = -score
        elif fault not in (None, "unsynced"):
            raise ValueError(f"unknown mask fault {fault!r}")
        _, idx = jax.lax.top_k(score, c.keep)
        out[c.name] = jnp.zeros((c.groups,), jnp.float32).at[idx].set(1.0)
    return out


def apply_masks(z: dict, masks: dict, classes: list) -> dict:
    z = dict(z)
    for c in classes:
        ch = jnp.repeat(masks[c.name], c.group_size)
        for key, axis in c.members + c.followers:
            z[key] = z[key] * _along(ch, axis, z[key].ndim).astype(
                z[key].dtype)
    return z


# ---------------------------------------------------------------------------
# state and rounds
# ---------------------------------------------------------------------------


def init_state(params: dict, hp: Hyper, classes: list) -> dict:
    """Every worker and consensus group starts at ``params``; duals and
    momenta at zero; masks keep every group."""
    flat = flatten(params)
    zeros = {k: jnp.zeros_like(v) for k, v in flat.items()}
    W = math.prod(hp.levels)
    counts, m = [], W
    for g in hp.levels:
        m //= g
        counts.append(m)
    return {
        "theta": [dict(flat) for _ in range(W)],
        "mom": [dict(zeros) for _ in range(W)],
        "u": [dict(zeros) for _ in range(W)],
        "z": [[dict(flat) for _ in range(n)] for n in counts],
        "v": [[dict(zeros) for _ in range(n)] for n in counts[:-1]],
        "rho": [{k: jnp.float32(hp.rho1 if b == 0 else hp.rho2)
                 for k in flat} for b in range(len(hp.levels))],
        "masks": {c.name: jnp.ones((c.groups,), jnp.float32)
                  for c in classes},
    }


@functools.partial(jax.jit, static_argnames=("arch_key", "momentum",
                                             "half_batch", "compute_dtype"))
def _worker_step(theta, mom, z1, u, rho1, images, labels, eta, *, arch_key,
                 momentum, half_batch, compute_dtype=None):
    """One prox-SGD step of one worker, computed in ``compute_dtype``
    (default: the parameters' own) and stored in the parameters' type."""
    arch = dict(arch_key)
    if half_batch:
        n = images.shape[0] // 2
        images, labels = images[:n], labels[:n]
    dt = theta["stem"].dtype
    cd = compute_dtype or dt

    def c(x):
        return x.astype(cd)
    lossv, g = jax.value_and_grad(
        lambda p: resnet.loss(arch, unflatten(p), images, labels))(
            {k: c(v) for k, v in theta.items()})
    new_t, new_m = {}, {}
    for k, th in theta.items():
        gt = g[k] + c(rho1[k]) * (c(th) - c(z1[k]) + c(u[k]))
        m = momentum * c(mom[k]) + gt
        new_m[k] = m.astype(dt)
        new_t[k] = th - (c(eta) * m).astype(dt)
    return new_t, new_m, lossv.astype(jnp.float32)


def _freeze(arch: dict):
    """Hashable form of an architecture dict (a static jit argument)."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in arch.items()))


def local_steps(state: dict, arch: dict, hp: Hyper, images, labels, eta,
                half_batch: bool = False, compute_dtype=None):
    """E proximal-SGD steps, one worker and one step at a time.

    ``images``/``labels`` are ``(E, W, B, ...)``.  Returns the new state
    and the ``(E,)`` worker-mean losses."""
    W = len(state["theta"])
    theta, mom = list(state["theta"]), list(state["mom"])
    losses = []
    for e in range(hp.local_steps):
        ls = []
        for w in range(W):
            z1 = state["z"][0][w // hp.levels[0]]
            theta[w], mom[w], lw = _worker_step(
                theta[w], mom[w], z1, state["u"][w], state["rho"][0],
                images[e, w], labels[e, w], eta, arch_key=_freeze(arch),
                momentum=hp.momentum, half_batch=half_batch,
                compute_dtype=compute_dtype)
            ls.append(lw)
        losses.append(sum(ls) / W)
    return dict(state, theta=theta, mom=mom), jnp.stack(losses)


@functools.partial(jax.jit, static_argnames=("hp", "classes", "frozen",
                                             "drop_top_exchange",
                                             "mask_fault"))
def consensus(state: dict, *, hp: Hyper, classes: tuple, frozen: bool,
              drop_top_exchange: bool = False,
              mask_fault: str | None = None) -> dict:
    """Phases 2-5 of Algorithm 1 over levels ``hp.levels`` (innermost
    first), the first boundary exchanged dense and every higher boundary
    carrying only the kept groups."""
    L, K = hp.levels, len(hp.levels)
    theta, u, rho = state["theta"], state["u"], state["rho"]
    z_old, v_old = state["z"], state["v"]
    keys = list(theta[0])
    W = len(theta)
    M1 = W // L[0]

    # z~1 (Eq. 9): node sums of theta + u, pulled toward the level above
    z1t = []
    for n in range(M1):
        out = {}
        for k in keys:
            buf = sum(theta[w][k] + u[w][k] for w in range(n * L[0],
                                                           (n + 1) * L[0]))
            num = rho[0][k] * buf
            den = rho[0][k] * L[0] + hp.weight_decay / M1
            if K > 1:
                up = z_old[1][n // L[1]][k] - v_old[0][n][k]
                num = num + rho[1][k] * up
                den = den + rho[1][k]
            out[k] = (num / den).astype(buf.dtype)
        z1t.append(out)
    masks = state["masks"] if frozen else select_masks(z1t, classes,
                                                       mask_fault)
    zs = [[apply_masks(z, masks, classes) for z in z1t]]

    # levels 2..K (Eq. 11): weighted means of z + v over each group
    for lvl in range(2, K + 1):
        g, kids, vk = L[lvl - 1], zs[-1], v_old[lvl - 2]
        child_w = math.prod(L[:lvl - 1])
        wsum = math.prod(L[:lvl])
        new = []
        for n in range(len(kids) // g):
            members = range(n * g, (n + 1) * g)
            if drop_top_exchange and lvl == K:
                members = [n * g] * g
            out = {}
            for k in keys:
                b = sum(child_w * (kids[c][k] + vk[c][k]) for c in members)
                if lvl == K:
                    out[k] = b / wsum
                else:
                    up = z_old[lvl][n // L[lvl]][k] - v_old[lvl - 1][n][k]
                    out[k] = ((rho[lvl - 1][k] * b + rho[lvl][k] * up)
                              / (rho[lvl - 1][k] * wsum + rho[lvl][k]))
                out[k] = out[k].astype(b.dtype)
            new.append(apply_masks(out, masks, classes))
        zs.append(new)

    # duals (Eq. 12-13)
    u_new = [{k: u[w][k] + (theta[w][k] - zs[0][w // L[0]][k]) for k in keys}
             for w in range(W)]
    v_new = [[{k: v_old[b][c][k] + (zs[b][c][k] - zs[b + 1][c // L[b + 1]][k])
               for k in keys} for c in range(len(zs[b]))]
             for b in range(K - 1)]

    # residuals and adaptive penalties, duals rescaled (Boyd 3.4.1)
    rho_new = []
    for b in range(K):
        lhs = theta if b == 0 else zs[b - 1]
        new_b = {}
        for k in keys:
            r2 = sum(jnp.sum(jnp.square(
                (lhs[i][k] - zs[b][i // L[b]][k]).astype(jnp.float32)))
                for i in range(len(lhs)))
            s2 = sum(jnp.sum(jnp.square(
                (zs[b][j][k] - z_old[b][j][k]).astype(jnp.float32)))
                for j in range(len(zs[b])))
            r_n = jnp.sqrt(r2)
            s_n = rho[b][k] * jnp.sqrt(s2)
            f = jnp.where(r_n > hp.adapt_mu * s_n, hp.adapt_tau,
                          jnp.where(s_n > hp.adapt_mu * r_n,
                                    1.0 / hp.adapt_tau, 1.0))
            new_b[k] = jnp.clip(rho[b][k] * f, 1e-8, hp.rho_max)
            scale = rho[b][k] / new_b[k]
            duals = u_new if b == 0 else v_new[b - 1]
            for d in duals:
                d[k] = d[k] * scale.astype(d[k].dtype)
        rho_new.append(new_b)
    return dict(state, u=u_new, z=zs, v=v_new, rho=rho_new, masks=masks)


def round_(state: dict, arch: dict, hp: Hyper, classes: tuple, images,
           labels, eta, *, frozen: bool, half_batch: bool = False,
           drop_top_exchange: bool = False, mask_fault: str | None = None,
           compute_dtype=None):
    """One outer round: E local steps, then the consensus.  Returns the
    new state and the ``(E,)`` losses."""
    state, losses = local_steps(state, arch, hp, images, labels, eta,
                                half_batch, compute_dtype)
    state = consensus(state, hp=hp, classes=classes, frozen=frozen,
                      drop_top_exchange=drop_top_exchange,
                      mask_fault=mask_fault)
    return state, losses
