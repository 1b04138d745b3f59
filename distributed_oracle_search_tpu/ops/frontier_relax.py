"""Delta-stepping frontier relaxation: the build kernel for high-diameter
irregular graphs (road networks).

The dense kernels (``bellman_ford``, ``ell_split``) sweep ALL N nodes
every iteration; iteration count ~ the max shortest-path hop length
(~graph diameter D). Road networks are the worst case for that product:
N large, D large (hundreds), frontiers tiny — a 264k-node network pays
D x N x K row-gathers while a CPU Dijkstra pays ~E log N per target
(the reference builds exactly that way: one Dijkstra per owned node
under OpenMP, reference ``README.md:88-95``). Round 3's bench measured
the dense split kernel at 0.65x ONE CPU core on that family; the dense
sweep simply does ~D x more relaxation work than the frontier carries.

This kernel keeps the relaxation *sparse* without leaving XLA's static
shapes — a device-resident **priority work queue** over nodes:

* ``prio`` int32 [N] — INF = idle; otherwise the node's wake priority:
  the smallest just-improved distance among its out-neighbors (a lower
  bound on the improvement it can still receive). Exactly Dijkstra's
  queue discipline, batched and approximate.
* Each iteration pops every node with ``prio <= min(prio) + delta``
  (delta-stepping's bucket, one compare + ``jnp.nonzero(size=F)`` —
  static shape, one compile), gathers ONLY those rows' out-edges
  ``[F, K]``, relaxes all B target columns at once ``[F, K, B]``,
  scatter-mins into the distance table, and scatter-mins the improved
  rows' new minima into their in-neighbors' ``prio`` (``[F, K_in]``).
  ``s_unroll`` relax sub-steps run per pop so chains inside one bucket
  settle without re-popping (measured 2x fewer iterations at S=2).
* Pad slots write index n -> dropped by scatter semantics; gathers clip
  to row n-1, whose redundant relaxation is masked out of the wake set.
  Queue overflow (> F ready) just leaves the rest armed: cleared bits
  are only the popped F, so the bucket drains over iterations —
  correctness never depends on F or delta (any pop order converges to
  the same unique fixed point; delta only controls how Dijkstra-like,
  and therefore how small, the re-expansion count is).

Why pop by distance and not FIFO: the graph's weight spread (highway
links ~500x a street block) makes hop order diverge from distance
order, and FIFO label-correcting re-expands whole subtrees each time a
shorter path lands — measured 8,870 pops vs 799 for delta-stepping on
the same 264k road graph.

Measured per-iteration cost on v5e (r04) is ~0.3 ms floor plus
~25-50 ns per gathered row, nearly independent of the row payload up
to ~1 KB — so the batch axis B is almost free while iterations are
expensive. The production defaults (F=2048, delta~32 x mean weight,
S=2, B=512; every deviation swept worse) build the 264k road graph at
23-41 rows/s across r04 captures (2.7-4.3x one CPU core's Dijkstra,
device-window dependent) and ~80-150 rows/s on 80-132k graphs — and
the whole loop runs in ONE ``lax.while_loop`` on device: no host round
trips, no data-dependent shapes.

The B columns share one queue (union frontier), so the kernel wants
(a) locality-ordered node ids and (b) id-clustered target batches —
both guaranteed on the build path: workers own contiguous id ranges
and road inputs are BFS/RCM-reordered first (``cli.reorder``). The
auto gate (``models.cpd.pick_build_kernel``) checks (a) explicitly via
:func:`locality_fraction` and falls back to the dense split kernel on
shuffled ids, where the union wavefront would span the whole graph.

Negative results (round 5, measured on the 264k road graph, same
device window as an 84-90 rows/s baseline — recorded so they are not
re-attempted): (1) a degree-split relax (short-ELL slice for all pops
+ full-width pass for popped hubs) ran 45 rows/s — the extra
nonzero/cumsum/scatter per iteration cost more than the 2.4x gather
reduction saved; (2) degree-BOUNDING the graph (hub tails moved to
zero-weight virtual-node chains, K 20 -> 6-8) kept bit-parity but
inflated iterations 1085 -> 3000-5100 (chain hops serialize across
pops; the unroll only re-relaxes POPPED rows) for 18-65 rows/s;
(3) XLA scatter hints (sorted/unique) on the dist scatter: 9.2 vs 5.8
ms/iter; (4) slot-looped relax accumulation (avoiding the [F, K, B]
temp): within noise. Ablations show no single op dominates — the
iteration is latency-bound through its dependency chain, so the
remaining lever is a fused Pallas pop+relax kernel, not op shaving.

Distances converge to the same unique fixed point as every other
kernel, and first-move extraction reuses the shared full-width pass —
tie-breaking stays bit-identical to the CPU oracle (bench asserts fm
parity on the 264k road graph).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .device_graph import JINF


@dataclasses.dataclass(frozen=True)
class FrontierGraph:
    """Host-side bundle for the delta-stepping relaxation."""

    in_nbr: np.ndarray   # int32 [N, K_in] k-th in-neighbor (pad: self)
    n: int
    f: int               # pop capacity per iteration
    delta: int           # bucket width (pop window above the queue min)
    s_unroll: int        # relax sub-steps per pop


#: pop capacity: iteration cost is ~flat in F below this on v5e (the
#: fixed loop floor dominates), while the measured optimum across 80k-
#: and 264k-node road graphs sat at 2048 (larger F gathers mostly pad
#: rows once the ready set thins out)
FRONTIER_CAPACITY = 2048

#: bucket width multiplier: delta ~ 32 x mean edge weight pops several
#: wavefront layers per iteration (amortizing the loop floor) while
#: keeping pops ordered enough that re-expansion stays ~1 (measured
#: best at 16-32k on graphs with mean weight ~575)
DELTA_MEAN_W_MULT = 32


def pick_delta(w: np.ndarray) -> int:
    """Bucket width from the weight distribution (power of two).

    Clamped to 2^29 < INF: correctness is delta-independent (any pop
    order converges), and an unclamped width on near-INF mean weights
    would overflow ``prio.min() + delta`` in int32."""
    mean_w = float(w.mean()) if len(w) else 1.0
    target = max(int(min(mean_w * DELTA_MEAN_W_MULT, 1 << 29)), 1)
    return min(1 << (target - 1).bit_length(), 1 << 29)


def locality_fraction(graph, window_mult: int = 8) -> float:
    """Fraction of edges with ``|dst - src|`` under ``window_mult*sqrt(N)``
    — the auto-gate's proxy for "wavefronts are id-coherent". RCM/BFS
    orderings of road graphs measure 0.4-0.6 here; shuffled ids 0.02
    (where the union frontier degenerates to the whole graph and the
    dense kernels win)."""
    if graph.m == 0:
        return 1.0
    win = window_mult * int(np.sqrt(max(graph.n, 1)))
    return float((np.abs(graph.dst - graph.src) < win).mean())


def frontier_graph(graph, f: int | None = None, delta: int | None = None,
                   s_unroll: int = 2) -> FrontierGraph:
    """Build the bundle from a :class:`~..data.graph.Graph`.

    An explicit ``delta`` is clamped to ``pick_delta``'s 2^29 ceiling:
    the pop window computes ``prio.min() + delta`` in int32, and an
    unclamped width would overflow it negative — an empty pop window
    that live-locks the build loop."""
    in_nbr, _ = graph.ell("in")
    return FrontierGraph(
        in_nbr=np.asarray(in_nbr, np.int32), n=graph.n,
        f=f if f is not None else FRONTIER_CAPACITY,
        delta=(min(int(delta), 1 << 29) if delta is not None
               else pick_delta(graph.w)),
        s_unroll=s_unroll)


@functools.lru_cache(maxsize=None)
def _frontier_dist_fn(n: int, f: int, delta: int, s_unroll: int,
                      max_iters: int):
    """Compiled [N, B] batch-minor delta-stepping relaxation."""
    # a queue pops at most F rows per iteration, so the dense kernels'
    # N-1 hop bound does not apply, and no tight a-priori bound exists
    # (a small F drains a saturated queue over many pops — a heuristic
    # limit silently truncated convergence in testing). Termination
    # without a limit is guaranteed: distances only decrease (bounded
    # below) and a node is re-armed only by an improvement, so the
    # queue must empty. max_iters=0 therefore means "run to
    # convergence" with only a runaway backstop; real builds converge
    # in ~1k pops (264k-node road graph, F=2048). Callers bound
    # runtime by batch sizing, and the auto gate's locality check is
    # what keeps iteration counts sane.
    limit = (1 << 30) if max_iters == 0 else max_iters

    @jax.jit
    def dist_to_targets_frontier(out_nbr, out_eid, w_pad, in_nbr, targets):
        b = targets.shape[0]
        valid = targets >= 0
        t_safe = jnp.where(valid, targets, 0)
        dist0 = jnp.full((n, b), JINF, jnp.int32)
        dist0 = dist0.at[t_safe, jnp.arange(b)].set(
            jnp.where(valid, jnp.int32(0), JINF))
        # arm the in-neighbors of every valid target at priority 0 (the
        # only rows with a non-INF relaxation input); pad rows write
        # index n -> dropped
        wake0 = jnp.where(valid[:, None], in_nbr[t_safe, :], n)
        prio0 = jnp.full(n, JINF, jnp.int32).at[wake0.reshape(-1)].min(0)

        def cond(st):
            i, _, prio = st
            return (prio.min() < JINF) & (i < limit)

        def body(st):
            i, dist, prio = st
            theta = prio.min() + delta
            # idle nodes (prio == JINF) must never match the pop window:
            # when theta >= JINF (near-INF weights push prio.min() within
            # delta of JINF), an unmasked pop fills the f slots with
            # low-id idle nodes and starves armed nodes forever —
            # a livelock until the iteration backstop. No overflow:
            # prio <= JINF (1e9) and delta <= 2^29, sum < int32 max.
            idx = jnp.nonzero((prio <= theta) & (prio < JINF),
                              size=f, fill_value=n)[0]
            live = idx < n
            prio = prio.at[idx].set(JINF)             # pads dropped
            nbr = out_nbr[idx]                        # [F, K] (pads clip)
            w = w_pad[out_eid[idx]]                   # [F, K]
            for _ in range(s_unroll):
                via = jnp.minimum(w[:, :, None] + dist[nbr, :], JINF)
                new = via.min(axis=1)                 # [F, B]
                imp = new < dist[idx]                 # [F, B]
                dist = dist.at[idx].min(new)          # pads dropped
                # wake in-neighbors of improved rows at the row's new
                # minimum (their relax input just reached that value);
                # unchanged/pad lanes write index n -> dropped
                newmin = jnp.where(imp, new, JINF).min(axis=1)
                ch = live & (newmin < JINF)
                wake = jnp.where(ch[:, None], in_nbr[idx], n)
                prio = prio.at[wake.reshape(-1)].min(
                    jnp.broadcast_to(newmin[:, None],
                                     wake.shape).reshape(-1))
            return i + 1, dist, prio

        _, d, _ = jax.lax.while_loop(
            cond, body, (jnp.int32(0), dist0, prio0))
        return d.T

    return dist_to_targets_frontier


def build_fm_columns_frontier(dg, fg: FrontierGraph, targets,
                              max_iters: int = 0,
                              extract_chunk: int = 512):
    """CPD shard build via the delta-stepping relaxation; fm extraction
    reuses the full-width pass (bit-identical tie-breaks).

    ``max_iters`` bounds queue POPS (not hop sweeps — a frontier
    iteration advances ~delta of distance, not one hop), 0 = converge.

    ``extract_chunk``: extraction runs in column slices of this many
    targets. The frontier's iteration cost amortizes over the batch
    (B=2048 measured ~10% more rows/s than 512 on the 264k road graph,
    and the fixed fetch/dispatch costs halve again), but a FUSED
    dist+extraction program at B=2048 OOMs: XLA's remat keeps all K
    slot-step temps of the extraction alive at once (20 x [N, B] int32
    = 40 GB observed). Slicing the extraction into separate dispatches
    after the dist solve restores the K-reuse scheduling at any B.
    """
    fn = _frontier_dist_fn(fg.n, fg.f, fg.delta, fg.s_unroll, max_iters)
    t = jnp.asarray(targets)
    dist = fn(dg.out_nbr, dg.out_eid, dg.w_pad,
              jnp.asarray(fg.in_nbr), t)
    b = int(t.shape[0])
    parts = [_extract_jit(dg, t[i:i + extract_chunk],
                          dist[i:i + extract_chunk])
             for i in range(0, b, extract_chunk)]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


@jax.jit
def _extract_jit(dg, t, d):
    """Standalone first-move extraction (one compiled program shared by
    every same-shape column slice of a chunked build)."""
    from .bellman_ford import first_move_from_dist

    return first_move_from_dist(dg, t, d)
