"""Agent graph topologies and the neighbor-exchange primitive.

LT-ADMM-CC runs over an **arbitrary undirected** agent graph G = (V, E)
(the paper's Assumption 1 only requires connectivity).  This module is the
single source of graph structure for the whole repo: ``core/admm.py``,
``core/baselines.py`` and the launch/bench layers contain no neighbor
arithmetic of their own — they consume the slot-based view defined here.

Slot-based neighbor model
-------------------------
All algorithm state carries a leading agent axis ``A``; edge state carries
``[A, S, ...]`` where ``S = topo.n_slots`` is the number of *neighbor
slots*.  Slot ``s`` of agent ``i`` either names one incident edge
``{i, j}`` (``slot_mask()[i, s]`` True, ``neighbor_table()[i, s] == j``) or
is inactive (mask False, neighbor table points at ``i`` itself).  Two
structural invariants make the slotting communication-friendly:

* **partial permutation** — within one slot the receive map
  ``i <- neighbor_table()[i, s]`` is injective on active agents, so each
  slot lowers to ONE ``collective-permute`` on a mesh axis;
* **uniform reverse slot** — ``reverse_slot[s]`` (the neighbor's slot that
  names the same edge from the other end) depends only on ``s``, not on the
  agent.  Ring uses directional slots (left/right, ``reverse_slot=(1,0)``);
  every edge-colored topology uses matching slots (``reverse_slot[s]==s``).

``Ring`` and ``Grid2D`` (torus) keep handcrafted directional slots — these
embed natively into ICI torus axes so every slot is a single-hop CP.
``Star``, ``Complete``, ``ErdosRenyi`` and ``SmallWorld`` build slots by
greedy edge coloring (each color class is a matching), giving
``n_slots <= 2 * max_degree - 1``; agents of lower degree carry masked
slots.

The ``Exchange`` primitive has two implementations with identical
semantics (bit-identical results — masked slots deliver the agent's own
message on both paths):

* ``axis=None`` — gather-by-index on the leading agent axis (static
  row slices: the routing tables are host constants).  Used for host simulation/tests.
* ``axis=<mesh axis>`` — ``shard_map`` over the agent mesh axis with one
  ``lax.ppermute`` per slot; every other mesh axis is left to the
  compiler.  This is the wire traffic the roofline counts.
"""
from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Any, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


# ---------------------------------------------------------------------------
# Protocol
# ---------------------------------------------------------------------------


@runtime_checkable
class Topology(Protocol):
    """Structural view of an undirected agent graph (see module docstring).

    Implementations are frozen dataclasses; all tables are host-side numpy
    (they become compile-time constants under jit).
    """

    n_agents: int

    @property
    def n_slots(self) -> int: ...

    # reverse_slot[s]: the neighbor's slot naming the same edge.
    reverse_slot: tuple

    def neighbor_table(self) -> np.ndarray:  # [A, S] int, self where masked
        ...

    def slot_mask(self) -> np.ndarray:  # [A, S] bool
        ...

    def degrees(self) -> np.ndarray:  # [A] int
        ...


def edge_set(topo) -> set:
    """Directed edge pairs {(i, j)} of a topology (both directions)."""
    nbr, mask = topo.neighbor_table(), topo.slot_mask()
    return {
        (i, int(nbr[i, s]))
        for i in range(topo.n_agents)
        for s in range(topo.n_slots)
        if mask[i, s]
    }


def validate(topo) -> None:
    """Check the structural invariants every Topology must satisfy."""
    nbr, mask = topo.neighbor_table(), topo.slot_mask()
    A, S = topo.n_agents, topo.n_slots
    assert nbr.shape == (A, S) and mask.shape == (A, S), (nbr.shape, S)
    for s in range(S):
        src = nbr[:, s]
        # inactive slots point at self
        assert (src[~mask[:, s]] == np.arange(A)[~mask[:, s]]).all(), s
        # the full receive map (active sources + inactive self-loops) must
        # be a permutation — this is exactly what Exchange._route hands to
        # lax.ppermute, which rejects duplicate sources
        assert sorted(src.tolist()) == list(range(A)), (
            f"slot {s} receive map is not a permutation"
        )
        assert (src[mask[:, s]] != np.arange(A)[mask[:, s]]).all(), (
            f"slot {s} active self-loop"
        )
    # symmetry through the uniform reverse slot
    for i in range(A):
        for s in range(S):
            if not mask[i, s]:
                continue
            j, rs = int(nbr[i, s]), topo.reverse_slot[s]
            assert mask[j, rs] and int(nbr[j, rs]) == i, (i, s, j, rs)
    # connectivity (Assumption 1)
    seen, stack = {0}, [0]
    adj = {i: set() for i in range(A)}
    for (i, j) in edge_set(topo):
        adj[i].add(j)
    while stack:
        for j in adj[stack.pop()]:
            if j not in seen:
                seen.add(j)
                stack.append(j)
    assert len(seen) == A, f"graph disconnected: reached {len(seen)}/{A}"


# ---------------------------------------------------------------------------
# Handcrafted directional topologies (single-hop on ICI torus axes)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Ring:
    """Undirected ring of ``n_agents`` agents (the paper's experiments).

    Directional slots: slot 0 = left/(i-1) edge, slot 1 = right/(i+1) edge.
    Degree d_i = 2 for every agent (n_agents >= 3), or 1 for n_agents == 2.
    """

    n_agents: int
    name = "ring"

    @property
    def n_slots(self) -> int:
        return 2

    @property
    def reverse_slot(self) -> tuple:
        # My left neighbor's right slot (1) is the edge (j -> i); vice
        # versa.  n_agents == 2 degenerates to a single slot-0 edge whose
        # reverse is slot 0 on the other end.
        return (0, 1) if self.n_agents == 2 else (1, 0)

    def neighbor_table(self) -> np.ndarray:
        ids = np.arange(self.n_agents)
        tab = np.stack([(ids - 1) % self.n_agents,
                        (ids + 1) % self.n_agents], axis=1)
        if self.n_agents == 2:  # degenerate: single edge, slot 1 masked
            tab[:, 1] = ids
        return tab

    def slot_mask(self) -> np.ndarray:
        mask = np.ones((self.n_agents, 2), dtype=bool)
        if self.n_agents == 2:
            mask[:, 1] = False
        return mask

    def degrees(self) -> np.ndarray:
        return self.slot_mask().sum(axis=1).astype(np.int64)


@dataclasses.dataclass(frozen=True)
class Grid2D:
    """2-D torus of ``rows x cols`` agents (both sides >= 3).

    Directional slots (west, east, north, south) — each a permutation of
    the agent set, so the grid keeps the ring's one-CP-per-slot property
    and embeds into a 2-D ICI mesh with single-hop exchanges.
    Agent id = r * cols + c.
    """

    rows: int
    cols: int
    name = "grid2d"

    def __post_init__(self):
        assert self.rows >= 3 and self.cols >= 3, (
            "Grid2D torus needs both sides >= 3 (smaller grids duplicate "
            "edges; use Ring or a GraphTopology instead)"
        )

    @property
    def n_agents(self) -> int:
        return self.rows * self.cols

    @property
    def n_slots(self) -> int:
        return 4

    # west<->east, north<->south
    reverse_slot = (1, 0, 3, 2)

    def neighbor_table(self) -> np.ndarray:
        r, c = np.divmod(np.arange(self.n_agents), self.cols)
        west = r * self.cols + (c - 1) % self.cols
        east = r * self.cols + (c + 1) % self.cols
        north = ((r - 1) % self.rows) * self.cols + c
        south = ((r + 1) % self.rows) * self.cols + c
        return np.stack([west, east, north, south], axis=1)

    def slot_mask(self) -> np.ndarray:
        return np.ones((self.n_agents, 4), dtype=bool)

    def degrees(self) -> np.ndarray:
        return np.full((self.n_agents,), 4, dtype=np.int64)


# ---------------------------------------------------------------------------
# Edge-list topologies via greedy edge coloring (matching slots)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _color_edges(n_agents: int, edges):
    """Greedy proper edge coloring; returns (neighbor_table, mask).

    Each color class is a matching, so within a slot the receive map is an
    involution on matched agents (trivially injective).  Greedy needs at
    most ``2 * max_degree - 1`` colors (Vizing guarantees ``max_degree + 1``
    exists; greedy trades tightness for simplicity and determinism).

    Cached: ``edges`` must be the normalized hashable tuple
    (``GraphTopology.from_edges`` guarantees this), and callers must not
    mutate the returned arrays.
    """
    edges = sorted({(min(i, j), max(i, j)) for (i, j) in edges})
    assert all(i != j for (i, j) in edges), "self-loops not allowed"
    used = [set() for _ in range(n_agents)]  # colors taken at each vertex
    colored = []  # (i, j, color)
    n_colors = 0
    for (i, j) in edges:
        c = 0
        while c in used[i] or c in used[j]:
            c += 1
        used[i].add(c)
        used[j].add(c)
        colored.append((i, j, c))
        n_colors = max(n_colors, c + 1)
    nbr = np.tile(np.arange(n_agents)[:, None], (1, max(n_colors, 1)))
    mask = np.zeros((n_agents, max(n_colors, 1)), dtype=bool)
    for (i, j, c) in colored:
        nbr[i, c], nbr[j, c] = j, i
        mask[i, c] = mask[j, c] = True
    return nbr, mask


@dataclasses.dataclass(frozen=True)
class GraphTopology:
    """Arbitrary undirected graph from an edge list (matching slots).

    ``reverse_slot[s] == s``: an edge occupies the same color/slot at both
    endpoints, so each slot's exchange is a pairwise swap (one CP).
    """

    n_agents: int
    edges: tuple  # normalized in __post_init__: sorted unique (i, j), i < j
    name: str = "graph"

    def __post_init__(self):
        # normalize regardless of construction path so degrees(), the
        # cached coloring, and dataclass hashing all agree
        es = tuple(
            sorted({(min(i, j), max(i, j)) for (i, j) in self.edges})
        )
        object.__setattr__(self, "edges", es)

    @classmethod
    def from_edges(cls, n_agents, edges, name="graph"):
        return cls(n_agents=n_agents, edges=tuple(edges), name=name)

    @property
    def n_slots(self) -> int:
        return self._tables()[0].shape[1]

    @property
    def reverse_slot(self) -> tuple:
        return tuple(range(self.n_slots))

    def _tables(self):
        return _color_edges(self.n_agents, self.edges)

    def neighbor_table(self) -> np.ndarray:
        return self._tables()[0]

    def slot_mask(self) -> np.ndarray:
        return self._tables()[1]

    def degrees(self) -> np.ndarray:
        d = np.zeros((self.n_agents,), dtype=np.int64)
        for (i, j) in self.edges:
            d[i] += 1
            d[j] += 1
        return d


def Star(n_agents: int) -> GraphTopology:
    """Hub-and-spoke: agent 0 is the hub (degree n-1), leaves have degree 1."""
    assert n_agents >= 2
    return GraphTopology.from_edges(
        n_agents, [(0, j) for j in range(1, n_agents)], name="star"
    )


def Complete(n_agents: int) -> GraphTopology:
    """Fully connected graph K_n."""
    assert n_agents >= 2
    return GraphTopology.from_edges(
        n_agents,
        [(i, j) for i in range(n_agents) for j in range(i + 1, n_agents)],
        name="complete",
    )


def ErdosRenyi(n_agents: int, p: float = 0.3, seed: int = 0) -> GraphTopology:
    """G(n, p) random graph, made connected by unioning a random
    Hamiltonian path (seeded, deterministic)."""
    rng = np.random.RandomState(seed)
    edges = {
        (i, j)
        for i in range(n_agents)
        for j in range(i + 1, n_agents)
        if rng.rand() < p
    }
    perm = rng.permutation(n_agents)
    for a, b in zip(perm, perm[1:]):  # connectivity backbone
        edges.add((min(a, b), max(a, b)))
    return GraphTopology.from_edges(n_agents, edges, name=f"erdos{p}")


def SmallWorld(n_agents: int, k: int = 4, p: float = 0.1,
               seed: int = 0) -> GraphTopology:
    """Watts–Strogatz: ring lattice with k nearest neighbors (k even),
    each lattice edge rewired with probability p (seeded)."""
    assert k % 2 == 0 and 2 <= k < n_agents
    rng = np.random.RandomState(seed)
    edges = {
        (min(i, (i + d) % n_agents), max(i, (i + d) % n_agents))
        for i in range(n_agents)
        for d in range(1, k // 2 + 1)
    }
    for e in sorted(edges):
        if rng.rand() >= p:
            continue
        i = e[0]
        cands = [j for j in range(n_agents)
                 if j != i and (min(i, j), max(i, j)) not in edges]
        if not cands:
            continue
        edges.discard(e)
        j = cands[rng.randint(len(cands))]
        edges.add((min(i, j), max(i, j)))
    # keep the graph connected: union a seeded Hamiltonian path backbone
    perm = rng.permutation(n_agents)
    for a, b in zip(perm, perm[1:]):
        edges.add((min(a, b), max(a, b)))
    return GraphTopology.from_edges(n_agents, edges, name=f"smallworld{p}")


# ---------------------------------------------------------------------------
# Registry / CLI parsing
# ---------------------------------------------------------------------------

TOPOLOGIES = ("ring", "grid2d", "star", "complete", "erdos", "smallworld")


def make_topology(spec: str, n_agents: int):
    """Build a topology from a CLI spec string.

    ``spec`` is ``name`` or ``name:k=v,k=v`` — e.g. ``ring``,
    ``grid2d:rows=4`` (cols inferred), ``erdos:p=0.4,seed=1``,
    ``smallworld:k=4,p=0.2``.
    """
    name, _, rest = spec.partition(":")
    kw = {}
    if rest:
        for item in rest.split(","):
            k, _, v = item.partition("=")
            kw[k.strip()] = v.strip()
    known = {"ring": (), "grid2d": ("rows",), "star": (), "complete": (),
             "erdos": ("p", "seed"), "smallworld": ("k", "p", "seed")}
    if name not in known:
        raise ValueError(
            f"unknown topology {spec!r}; choose from {TOPOLOGIES}"
        )
    extra = set(kw) - set(known[name])
    if extra:  # a typo'd param silently running with defaults is worse
        raise ValueError(
            f"topology {name!r} got unknown params {sorted(extra)}; "
            f"accepts {list(known[name])}"
        )
    if name == "ring":
        return Ring(n_agents)
    if name == "grid2d":
        rows = int(kw.get("rows", round(np.sqrt(n_agents))))
        assert n_agents % rows == 0, (
            f"grid2d: n_agents={n_agents} not divisible by rows={rows}"
        )
        return Grid2D(rows, n_agents // rows)
    if name == "star":
        return Star(n_agents)
    if name == "complete":
        return Complete(n_agents)
    if name == "erdos":
        return ErdosRenyi(n_agents, p=float(kw.get("p", 0.3)),
                          seed=int(kw.get("seed", 0)))
    return SmallWorld(n_agents, k=int(kw.get("k", 4)),
                      p=float(kw.get("p", 0.1)),
                      seed=int(kw.get("seed", 0)))


# ---------------------------------------------------------------------------
# Exchange primitive
# ---------------------------------------------------------------------------


def _take_rows(x, src_ids):
    """``x[src_ids]`` on axis 0 for a host-constant index array, as one
    static slice per row.  A gather would do the same, but XLA's TPU
    compiler splits a gather of rows this wide (a packed parameter plane)
    into 32K-element pieces, and its compile time then grows with the
    plane width."""
    src = np.asarray(src_ids)
    rows = [x[int(i)] for i in src.reshape(-1)]
    return jnp.stack(rows).reshape(src.shape + x.shape[1:])


def _take_tree(tree, src_ids):
    return jax.tree.map(lambda x: _take_rows(x, src_ids), tree)


def _ppermute_tree(tree, axis_name, perm):
    return jax.tree.map(
        lambda x: jax.lax.ppermute(x, axis_name, perm), tree
    )


def _shard_map(fn, mesh, axis):
    """Manual over the agent axis only: every non-agent mesh axis is
    left to the compiler."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=P(axis), out_specs=P(axis),
        axis_names={axis},
    )


@dataclasses.dataclass(frozen=True)
class Exchange:
    """Neighbor exchange over any ``Topology``, optionally bound to a mesh
    axis.

    ``axis``: mesh axis name the agent dim is sharded over, or None for the
    pure-jnp gather implementation (host simulation / tiny tests).
    ``gather=True`` keeps the gather routing on a mesh-bound exchange: the
    host-simulated exchange on an agent-sharded placement, which the
    ppermute path is checked against.

    Masked slots deliver the agent's OWN message (a self-loop) on both
    implementations, so the two paths are bit-identical everywhere; the
    algorithm layer masks those slots out of the math.

    ``faults`` (a ``core.faults.FaultPlane``, duck-typed — this module
    never imports it) arms the slot-batched paths: when set AND a
    ``round_index`` is passed, routed *sealed* payloads get seeded
    faults injected post-routing via ``faults.inject``.  Calls without
    ``round_index`` (e.g. the NAK control plane) stay reliable.
    """

    topo: Any
    axis: str | None = None
    mesh: Any = None  # jax.sharding.Mesh when axis is not None
    faults: Any = None  # core.faults.FaultPlane | None
    gather: bool = False

    def gather_from_neighbors(self, per_agent_tree):
        """Every agent broadcasts one message; returns tuple over slots of
        the received messages, each with leading dim A.

        Slot s of the result holds the message sent by my slot-s neighbor
        (my own message where slot s is masked).
        """
        nbr = self.topo.neighbor_table()
        return tuple(
            self._route(per_agent_tree, nbr[:, s])
            for s in range(self.topo.n_slots)
        )

    def exchange_edges(self, per_slot_trees):
        """Edge-directed exchange: ``per_slot_trees[s]`` is what each agent
        sends to its slot-s neighbor.  Returns per-slot received messages:
        result[s] = message my slot-s neighbor sent on its reverse slot.
        """
        nbr = self.topo.neighbor_table()
        out = []
        for s in range(self.topo.n_slots):
            rs = self.topo.reverse_slot[s]
            out.append(self._route(per_slot_trees[rs], nbr[:, s]))
        return tuple(out)

    # ---- slot-batched variants (packed-plane hot path) --------------------
    #
    # Same semantics as the tuple-of-slots methods above, but the slot
    # axis rides INSIDE the arrays (``[A, S, ...]``), so the host path is
    # one routing op for all slots and the mesh path runs its per-slot
    # ppermutes inside a single shard_map (one program, S collectives).

    def gather_batched(self, per_agent_tree, round_index=None):
        """Broadcast exchange, slot-batched: leaves ``[A, ...]`` in,
        ``[A, S, ...]`` out with ``out[i, s] = in[neighbor_table()[i, s]]``
        (own message on masked slots, as always)."""
        nbr = self.topo.neighbor_table()
        if self.axis is None or self.gather:
            out = _take_tree(per_agent_tree, nbr)  # [A, S, ...]
            return self._maybe_inject(out, round_index)
        A, S = self.topo.n_agents, self.topo.n_slots
        perms = [
            [(int(nbr[i, s]), i) for i in range(A)] for s in range(S)
        ]

        def body(tree):
            outs = [_ppermute_tree(tree, self.axis, p) for p in perms]
            return jax.tree.map(
                lambda *xs: jnp.stack(xs, axis=1), *outs
            )

        out = _shard_map(body, self.mesh, self.axis)(per_agent_tree)
        return self._maybe_inject(out, round_index)

    def exchange_batched(self, edge_tree, round_index=None):
        """Edge-directed exchange, slot-batched: leaves ``[A, S, ...]`` in
        and out, ``out[i, s] = in[neighbor_table()[i, s],
        reverse_slot[s]]`` — every slot's swap in ONE gather on the host
        path (flat ``[A * S]`` index arithmetic)."""
        nbr = self.topo.neighbor_table()
        A, S = self.topo.n_agents, self.topo.n_slots
        rev = self.topo.reverse_slot
        if self.axis is None or self.gather:
            flat_idx = (
                nbr * S + np.asarray(rev, dtype=nbr.dtype)[None, :]
            )  # [A, S]: sender agent * S + sender slot

            def route(x):
                x2 = jnp.reshape(x, (A * S,) + x.shape[2:])
                return _take_rows(x2, flat_idx)

            return self._maybe_inject(
                jax.tree.map(route, edge_tree), round_index)
        perms = [
            [(int(nbr[i, s]), i) for i in range(A)] for s in range(S)
        ]

        def body(tree):
            outs = [
                _ppermute_tree(
                    jax.tree.map(lambda x: x[:, rev[s]], tree),
                    self.axis,
                    perms[s],
                )
                for s in range(S)
            ]
            return jax.tree.map(
                lambda *xs: jnp.stack(xs, axis=1), *outs
            )

        out = _shard_map(body, self.mesh, self.axis)(edge_tree)
        return self._maybe_inject(out, round_index)

    def _maybe_inject(self, routed, round_index):
        if self.faults is None or round_index is None:
            return routed
        return self.faults.inject(routed, self.topo, round_index)

    def _route(self, tree, src_ids):
        """recv[i] = sent[src_ids[i]] — src_ids must be a partial
        permutation extended with self-loops (Topology invariant)."""
        if self.axis is None or self.gather:
            return _take_tree(tree, src_ids)
        perm = [(int(src_ids[i]), i) for i in range(self.topo.n_agents)]
        fn = partial(_ppermute_tree, axis_name=self.axis, perm=perm)
        return _shard_map(fn, self.mesh, self.axis)(tree)


# ---------------------------------------------------------------------------
# Gossip / mixing weights for the baselines
# ---------------------------------------------------------------------------


def metropolis_weights(topo) -> np.ndarray:
    """Metropolis–Hastings mixing matrix W for an arbitrary topology:
    W_ij = 1 / (1 + max(d_i, d_j)) on edges, diagonal absorbs the rest.
    Symmetric, doubly stochastic, spectral gap > 0 on connected graphs."""
    A = topo.n_agents
    d = topo.degrees()
    W = np.zeros((A, A))
    for (i, j) in edge_set(topo):
        W[i, j] = 1.0 / (1.0 + max(int(d[i]), int(d[j])))
    W[np.diag_indices(A)] = 1.0 - W.sum(axis=1)
    return W
