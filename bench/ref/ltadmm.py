"""Plain reference of LT-ADMM-CC training rounds (paper Algorithm 1).

Written from the paper and from the documented conventions of the
system under test, and importing nothing of it:

* data: each agent's ``m_local`` sequences of ``seq_len + 1`` tokens,
  a share ``heterogeneity`` of them drawn from the agent's own band of
  the vocabulary;
* the packed plane: every weight flattened in sorted-path order into
  one vector per agent, which is what a message carries;
* the local phase: an SVRG anchor gradient over the agent's sequences,
  then ``tau`` steps ``phi <- phi - gamma * g - beta * (r^2 rho d x -
  r sum_j z_ij)`` with ``g = grad_B(phi) - grad_B(anchor) +
  grad(anchor)`` on a minibatch drawn from the round key;
* the b-bit quantizer with stochastic rounding, whose rounding offsets
  come from Threefry-2x32 counters under a seed pair folded from the
  round key, the sender and the receiver (all ones for a broadcast);
* error feedback, the exchange over the agent graph and the z update
  (paper eqs. (4)-(6)), with eta = 1.

``run`` returns the consensus mean after the logged rounds, the mean
loss and the consensus error at each log point, and the first round's
anchor gradient per leaf.  Each agent's local phase is one jitted call,
so the reference holds one agent's activations at a time.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.ref import decoder

_PARITY = np.uint32(0x1BD11BDA)
_ROT = ((13, 15, 26, 6), (17, 29, 16, 24))
BROADCAST = 0xFFFFFFFF
ROUND_KEY_BASE = 1000  # round r runs under jax.random.key(1000 + r)
SALT_BATCH, SALT_X, SALT_Z = 7, 11, 13


# ---- Threefry-2x32 (20 rounds) --------------------------------------------


def _u32(x):
    return jnp.asarray(np.uint32(x) if isinstance(x, int) else x).astype(
        jnp.uint32)


def threefry2x32(k0, k1, c0, c1):
    k0, k1, x0, x1 = _u32(k0), _u32(k1), _u32(c0), _u32(c1)
    ks = (k0, k1, k0 ^ k1 ^ _PARITY)
    x0, x1 = x0 + ks[0], x1 + ks[1]
    for i in range(5):
        for rot in _ROT[i % 2]:
            x0 = x0 + x1
            x1 = ((x1 << np.uint32(rot)) | (x1 >> np.uint32(32 - rot))) ^ x0
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + np.uint32(i + 1)
    return x0, x1


def message_seed(base_key, sender, receiver):
    """Seed pair of one message: the key's two words, then one cipher
    block per id with the fold depth as the second counter word."""
    data = jax.random.key_data(base_key)
    s0, s1 = data[0], data[1]
    for depth, ident in enumerate((sender, receiver)):
        s0, s1 = threefry2x32(s0, s1, ident, depth)
    return s0, s1


# ---- the b-bit quantizer -----------------------------------------------------


@functools.partial(jax.jit, static_argnames=("bits",))
def quantize(s0, s1, x, *, bits):
    """-> ``(q int32 [n], scale)``; element e rounds with the top 24 bits
    of block ``(e, 0)`` under the seed pair as its offset in [0, 1)."""
    levels = np.float32(2 ** (bits - 1) - 1)
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf)), jnp.finfo(jnp.float32).tiny)
    bits_e, _ = threefry2x32(s0, s1, jnp.arange(x.shape[0], dtype=jnp.uint32), 0)
    kappa = (bits_e >> np.uint32(8)).astype(jnp.int32).astype(jnp.float32) \
        * np.float32(2.0 ** -24)
    q = jnp.minimum(jnp.floor(jnp.abs(xf) * (levels / scale) + kappa), levels)
    return (jnp.sign(xf) * q).astype(jnp.int32), scale


def dequantize(q, scale, bits, dtype):
    levels = np.float32(2 ** (bits - 1) - 1)
    return (scale * q.astype(jnp.float32) / levels).astype(dtype)


def message_bytes(n, bits):
    """Wire bytes of one quantized message: the packed integers and one
    float32 scale."""
    return (n * bits + 7) // 8 + 4


# ---- data, graph, plane ------------------------------------------------------


def make_tokens(seed, agents, m_local, seq_len, vocab, heterogeneity):
    """``[A, m_local, seq_len + 1]`` int32 tokens of every agent."""
    keys = jax.random.split(jax.random.key(seed), agents)
    band = vocab // agents
    shape = (m_local, seq_len + 1)
    out = []
    for aid in range(agents):
        k1, k2, k3 = jax.random.split(keys[aid], 3)
        base = jax.random.randint(k1, shape, 0, vocab)
        pref = aid * band + jax.random.randint(k2, shape, 0, band)
        use = jax.random.uniform(k3, shape) < heterogeneity
        out.append(jnp.where(use, pref, base).astype(jnp.int32))
    return jnp.stack(out)


def neighbours(topology, agents):
    """Sorted neighbour list of every agent of an undirected graph."""
    if topology == "complete":
        return [[j for j in range(agents) if j != i] for i in range(agents)]
    raise ValueError(f"reference has no topology {topology!r}")


class Plane:
    """Sorted-path flattening of a weight dict into one vector."""

    def __init__(self, weights):
        self.paths = list(weights)
        self.shapes = [weights[p].shape for p in self.paths]
        self.sizes = [math.prod(s) for s in self.shapes]
        self.n = sum(self.sizes)

    def pack(self, weights):
        return jnp.concatenate([jnp.ravel(weights[p]) for p in self.paths])

    def unpack(self, flat):
        out, off = {}, 0
        for p, shape, size in zip(self.paths, self.shapes, self.sizes):
            out[p] = jnp.reshape(flat[off:off + size], shape)
            off += size
        return out


# ---- the round -------------------------------------------------------------


class Reference:
    """LT-ADMM-CC on ``agents`` agents from one seed.

    ``model``: the configuration's sizes; ``net``: the module of its
    plain model (``init_weights``, ``loss``; ``decoder`` by default); ``train``:
    the solver, graph and mix numbers (``agents``, ``topology``, ``rho``,
    ``beta``, ``gamma``, ``r``, ``eta``, ``bits``, ``tau``,
    ``batch_size``, ``m_local``, ``seq_len``, ``heterogeneity``).
    ``plane_dtype``/``compute_dtype`` set the precision of the solver
    state and of the model's products.  ``fault`` plants one of the
    faults a round can have: ``"half_batch"`` (anchor gradient over
    the first half of the agent's sequences), ``"no_exchange"`` (every
    received message reads as zero).
    """

    def __init__(self, model, train, *, net=decoder, plane_dtype=jnp.float32,
                 compute_dtype=jnp.float32, fault=None):
        if train["eta"] != 1.0:
            raise ValueError("reference implements eta = 1 only")
        if fault not in (None, "half_batch", "no_exchange"):
            raise ValueError(f"unknown fault {fault!r}")
        self.model, self.train, self.fault = model, train, fault
        self.pdt, self.cdt = plane_dtype, compute_dtype
        self.nbrs = neighbours(train["topology"], train["agents"])
        self.net = net
        self._grad = jax.jit(jax.grad(
            lambda w, t: net.loss(model, w, t, compute_dtype)))
        self._loss = jax.jit(
            lambda w, t: net.loss(model, w, t, compute_dtype))

    # one agent's local phase: anchor, then tau variance-reduced steps
    @functools.partial(jax.jit, static_argnums=(0, 1))
    def _local(self, plane, x, corr, tokens, round_key, aid):
        tr = self.train
        m = tokens.shape[0]
        anchor_rows = m // 2 if self.fault == "half_batch" else m

        def seq_grad(w_flat, seqs):
            return plane.pack(self._grad(plane.unpack(w_flat), seqs)).astype(
                self.pdt)

        per_seq = jax.lax.map(lambda s: seq_grad(x, s[None]),
                              tokens[:anchor_rows])
        anchor_grad = jnp.mean(per_seq.astype(jnp.float32), 0).astype(self.pdt)
        kb = jax.random.fold_in(jax.random.fold_in(round_key, SALT_BATCH), aid)

        def body(phi, t):
            idx = jax.random.randint(jax.random.fold_in(kb, t),
                                     (tr["batch_size"],), 0, m)
            g_phi = seq_grad(phi, tokens[idx])
            if anchor_rows == m:
                g_anc = jnp.mean(per_seq[idx].astype(jnp.float32), 0)
            else:
                g_anc = seq_grad(x, tokens[idx]).astype(jnp.float32)
            g = (g_phi - g_anc.astype(self.pdt)) + anchor_grad
            return phi - tr["gamma"] * g - corr, None

        phi, _ = jax.lax.scan(body, x, jnp.arange(tr["tau"]))
        return phi, anchor_grad

    def _mean_loss(self, plane, xbar_flat, tokens):
        w = plane.unpack(xbar_flat)
        per_agent = []
        for a in range(tokens.shape[0]):
            seqs = [float(self._loss(w, tokens[a, i:i + 1]))
                    for i in range(tokens.shape[1])]
            per_agent.append(sum(seqs) / len(seqs))
        return sum(per_agent) / len(per_agent)

    def run(self, seed, rounds, log_every):
        """Train ``rounds`` rounds from ``seed``; returns ``weights0``,
        ``xbar`` (consensus mean, per leaf), ``losses`` and ``consensus``
        (one per log point) and ``grad0`` (mean first anchor gradient,
        per leaf)."""
        tr, mdl = self.train, self.model
        A = tr["agents"]
        weights0 = self.net.init_weights(mdl, seed + 1)
        tokens = make_tokens(seed, A, tr["m_local"], tr["seq_len"],
                             mdl["vocab_size"], tr["heterogeneity"])
        plane = Plane(weights0)
        x0 = plane.pack(weights0).astype(self.pdt)
        x = [x0] * A
        x_hat = [x0] * A
        zero = jnp.zeros_like(x0)
        z = [{j: zero for j in self.nbrs[i]} for i in range(A)]
        s = [{j: zero for j in self.nbrs[i]} for i in range(A)]
        s_tilde = [{j: zero for j in self.nbrs[i]} for i in range(A)]
        x_hat_nbr = [{j: x0 for j in self.nbrs[i]} for i in range(A)]
        bits, rrho = tr["bits"], tr["r"] * tr["rho"]
        losses, consensus, grad0 = [], [], None
        for rnd in range(rounds):
            key = jax.random.key(ROUND_KEY_BASE + rnd)
            kx = jax.random.fold_in(key, SALT_X)
            kz = jax.random.fold_in(key, SALT_Z)
            x_new, anchors = [], []
            for i in range(A):
                z_sum = functools.reduce(jnp.add, z[i].values())
                corr = tr["beta"] * (
                    tr["r"] ** 2 * tr["rho"] * len(self.nbrs[i]) * x[i]
                    - tr["r"] * z_sum)
                phi, g0 = self._local(plane, x[i], corr, tokens[i], key, i)
                x_new.append(phi)
                anchors.append(g0)
            if grad0 is None:
                grad0 = plane.unpack(
                    sum(g.astype(jnp.float32) for g in anchors) / A)
            # x-messages: one broadcast per sender, error feedback on x_hat
            dx = []
            for i in range(A):
                q, sc = quantize(*message_seed(kx, i, BROADCAST),
                                 x_new[i] - x_hat[i], bits=bits)
                dx.append(dequantize(q, sc, bits, self.pdt))
            x_hat_new = [x_hat[i] + dx[i] for i in range(A)]
            # z-messages: one per directed edge, error feedback on s
            rec = {}
            for i in range(A):
                for j in self.nbrs[i]:
                    q, sc = quantize(*message_seed(kz, i, j),
                                     z[i][j] - s[i][j], bits=bits)
                    rec[i, j] = dequantize(q, sc, bits, self.pdt)
            z_hat_own = [{j: s[i][j] + rec[i, j] for j in self.nbrs[i]}
                         for i in range(A)]
            lost = self.fault == "no_exchange"
            x_hat_nbr = [{j: x_hat_nbr[i][j] + (zero if lost else dx[j])
                          for j in self.nbrs[i]} for i in range(A)]
            s_tilde = [{j: s_tilde[i][j] + (zero if lost else rec[j, i])
                        for j in self.nbrs[i]} for i in range(A)]
            z = [{j: 0.5 * (z_hat_own[i][j] - s_tilde[i][j])
                  + rrho * x_new[i] - rrho * (x_hat_new[i] - x_hat_nbr[i][j])
                  for j in self.nbrs[i]} for i in range(A)]
            s, x, x_hat = z_hat_own, x_new, x_hat_new
            if (rnd + 1) % log_every == 0:
                xs = [xi.astype(jnp.float32) for xi in x]
                xbar = sum(xs) / A
                losses.append(self._mean_loss(plane, xbar, tokens))
                consensus.append(float(sum(
                    jnp.sum(jnp.square(xi - xbar)) for xi in xs)))
        xbar = sum(xi.astype(jnp.float32) for xi in x) / A
        return {"weights0": weights0, "xbar": plane.unpack(xbar),
                "losses": losses, "consensus": consensus, "grad0": grad0}
