"""Cycle-level interconnection-network simulator in JAX (CAMINOS-equivalent).

Model (documented deviations from the paper's flit-level CAMINOS setup in
docs/DESIGN.md): slotted time — one slot = one 16-flit packet serialization
on a link.  Input-queued switches with ``V`` virtual channels per port and
``Q``-packet queues, credit-based flow control (a packet advances only if the
downstream input queue for its next VC has room), separable random-priority
output arbitration (one grant per output port per slot), per-input-port VC
pre-arbitration (one candidate VC per input port per slot), unbounded
ejection, per-endpoint injection queues (one NIC per endpoint, one packet
injected per slot max).

Routing is evaluated *inside* the jitted step on compact precomputed tables:

* ``polarized``        — the paper's adapted Polarized routing (Section 4.3.2)
  with VC = updown-phase = hops // 2 (1 VC per Up-Down pass — the halved
  deadlock resources of Section 4.3).  Gathers two fused route rows (to
  source and to target) per requester.
* ``minimal_adaptive`` — adaptive minimal (Fat-Tree / OFT "MIN").
* ``ksp``              — randomized minimal-DAG walk (models KSP's random
  choice among precomputed shortest paths).
* ``ugal``             — UGAL-L with Valiant intermediate leaf (Dragonfly).
* ``valiant``          — always-Valiant.

The minimal policies never gather ``[P]``-wide distance rows: the candidate
port set for (switch, target leaf) is static, so ``build_tables`` packs it
into uint32 bitmasks (``RoutingTables.min_mask``) and the step does one
word gather plus a bit test per requester.  ``polarized`` and ``degraded``
also read away bits and distances; they keep one fused table instead
(``route_rows``: toward bits, away bits and the int16 distance of a
(leaf, switch) pair in one row, :func:`repro.core.routing.pack_route_rows`),
so a requester gathers one row per leaf it classifies against.

The step is engineered to be compute-bound, not gather/scatter-bound:

* **O(S) packet free-list** — the pool allocator is a ring buffer
  (``fl_buf``/``fl_head``/``fl_len``) with O(S) pops at inject and O(NR)
  pushes at eject, replacing the per-slot ``jnp.nonzero`` scan over the
  whole (up to 2M-entry) pool.  The free *set* is the ring window
  (``Simulator.free_ids``); in-flight count is ``pool - fl_len``.
  Per-packet attributes are bit-packed (``p_sd`` = src leaf << 16 | dst
  leaf, ``p_bh`` = born slot << 8 | hops) to halve pool scatter/gather
  traffic.
* **Donated buffers** — ``run_chunk`` / ``run_chunk_batch`` /
  ``_completion_loop`` donate the state pytree, so chunked runs update
  state in place instead of double-buffering the whole simulator.  A state
  dict passed to any of these is *consumed*: do not reuse it afterwards
  (keep the returned dict instead).
* **Pluggable arbitration backend** — ``SimConfig.backend`` selects
  ``"xla"`` (default, inline jnp) or ``"pallas"`` (the fused per-switch
  arbitration kernel in ``repro.kernels.switch_arb``, interpret-mode on
  CPU).  Both backends are bitwise-identical per replica.

Everything is fixed-shape; throughput/latency runs are jitted ``lax.scan``
chunks, and completion runs are a single device-side ``lax.while_loop``
over chunks (the ``ejected >= expected`` check never round-trips to the
host, and the exact completion slot is recorded from the ejection-counter
crossing).  Replication is a first-class compiled axis: ``make_batch_state``
stacks R independently-seeded states along a leading replica dimension and
``run_*_batch`` drive all replicas through one ``jax.vmap``-ed executable.

Collectives execute as compiled workload programs (``repro.workloads``):
``Traffic("program")`` carries the static schedule shape, the compiled
``partner``/``packets``/``expected`` arrays ride in the state, and
``run_program`` drives every phase of every replica through one
``lax.while_loop`` with an on-device phase scheduler
(``_advance_program``) — ``schedule="barrier"`` replays the legacy
per-phase host loop bitwise, ``schedule="window"`` pipelines rounds.
"""
from __future__ import annotations

import contextlib
import copy
import dataclasses
import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.routing import (FUSED_POLICIES, RoutingTables, pack_route_rows,
                            route_row_words)
from ..runtime import tracing
from ..workloads.patterns import (ARRIVAL_PATTERNS, BERNOULLI_PATTERNS,
                                  bounded_pareto_mean, check_arrival,
                                  check_pattern)

BIG = jnp.float32(1e9)

BACKENDS = ("xla", "pallas")

# percentile ladder of the latency-family drivers: median, p99, and the
# serving-SLO tails (p999 / p9999)
LATENCY_QS = (0.5, 0.99, 0.999, 0.9999)

# counter of the slots a run entry stepped, per replica; counted only
# where the number is already on the host (windowed programs from a fresh
# state, and the warm + measure drivers), never through a new transfer:
# barrier programs, bounded segments and run_completion go uncounted
SLOTS_STEPPED = "engine.slots_stepped"
# beside it: routing-table rows the route phase gathered in those slots
# (slots x speedup x requesters x rows per requester), from static numbers
ROUTE_ROWS = "engine.route_rows"


@contextlib.contextmanager
def _quiet_cpu_donation():
    """Silence "Some donated buffers were not usable" on the CPU backend,
    where jax warns once per compile and would drown the test output.  On
    an accelerator the warning is the one sign that a donated state or
    table is being copied instead of updated in place, so it stays
    visible there.  Scoped to this engine's own compiles — the
    process-global filter is left alone."""
    if jax.default_backend() != "cpu":
        yield
        return
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield


# ---------------------------------------------------------------------- #
# configuration
# ---------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class SimConfig:
    policy: str = "polarized"
    vcs: int = 4                 # V
    queue_depth: int = 8         # Q packets per (port, VC) at input
    out_queue: int = 4           # packets per (port, VC) at output
    speedup: int = 2             # crossbar sub-rounds per slot
    endpoint_queue: int = 4      # QE packets per NIC
    max_hops: int = 8            # routing hop bound (2D* - 2 for polarized)
    deroute_penalty: float = 8.0
    pool: Optional[int] = None   # packet pool size (default: auto)
    hist_bins: int = 4096        # latency histogram bins (slots)
    seed: int = 0
    backend: str = "xla"         # "xla" | "pallas" arbitration backend


@dataclasses.dataclass(frozen=True)
class Traffic:
    """Traffic program.  ``pattern`` is validated against the shared
    workload-pattern registry (:mod:`repro.workloads.patterns`): the
    Bernoulli families (uniform | rep | rsp | bu | mice_elephant | tornado
    | shift | hotspot | bursty), ``all2all``, or the engine-level
    ``phase`` / ``program`` patterns.  Unknown names raise here, at
    construction — never at trace time.

    * Bernoulli patterns use ``load`` (packets/slot/endpoint).  The
      adversarial families add: ``shift`` (static permutation
      ``(e + shift) mod S``), ``tornado`` (leaf-level half-rotation),
      ``hotspot`` (``hot_frac`` of messages incast onto endpoints
      ``0..hot_count-1``), ``bursty`` (on-off Markov modulation with mean
      burst length ``burst_len`` slots and in-burst intensity
      ``burst_load``; long-run offered load stays ``load``).
    * ``arrival``: open-loop serving source.  ``process`` picks the
      arrival generator (``poisson`` — Bernoulli(load) single-packet
      arrivals; ``pareto`` — bounded-Pareto batch sizes (shape
      ``pareto_alpha``, cap ``pareto_cap``) with the arrival probability
      calibrated so the long-run offered load stays ``load``; ``diurnal``
      — sinusoidal rate modulation with relative amplitude
      ``diurnal_amp`` and period ``diurnal_period`` slots).  Each endpoint
      holds an ``arr_depth``-deep FIFO of pending request batches;
      arrivals that find it full are dropped (``arr_drop``) instead of
      back-pressuring the source — that open loop is what distinguishes
      serving traffic from the Bernoulli families, whose idle-endpoint
      gating silently caps offered load at service capacity.  Packet
      latency is measured from the batch's *arrival* slot (``msg_birth``),
      so source queueing shows up in the histogram.
    * ``all2all``: each endpoint sends ``rounds`` single-packet messages to
      (e + r + 1) mod S, free-running (no round synchronization).
    * ``phase``: each endpoint sends ``phase_packets`` packets to
      ``partner[e]`` (the legacy hand-patched single-exchange idiom).
    * ``program``: a compiled :class:`repro.workloads.CompiledProgram` of
      ``n_phases`` phases executed by the on-device phase scheduler under
      ``schedule`` (``"barrier"`` replays the host loop bitwise;
      ``"window"`` lets endpoints run ``window`` phases ahead of the
      globally-completed phase).  The program arrays live in the *state*
      (``make_program_state``); only the static shape/schedule lives here,
      so runs of same-shaped programs share one compiled executable.
    """
    pattern: str = "uniform"
    load: float = 1.0
    rounds: int = 0
    phase_packets: int = 0
    elephant_frac: float = 0.1   # fraction of messages that are elephants
    elephant_size: int = 16
    # adversarial Bernoulli knobs
    shift: int = 1               # shift: dst = (e + shift) mod S
    hot_frac: float = 0.1        # hotspot: fraction of incast messages
    hot_count: int = 1           # hotspot: number of hot endpoints
    burst_len: float = 8.0       # bursty: mean ON duration (slots)
    burst_load: float = 1.0      # bursty: injection probability while ON
    # open-loop arrival source ("arrival" pattern) knobs
    process: str = "poisson"     # poisson | pareto | diurnal
    pareto_alpha: float = 1.5    # bounded-Pareto shape (> 1)
    pareto_cap: int = 64         # bounded-Pareto batch-size cap (packets)
    diurnal_amp: float = 0.5     # relative rate-modulation amplitude [0,1]
    diurnal_period: int = 512    # modulation period (slots, >= 2)
    arr_depth: int = 8           # per-endpoint pending-batch FIFO depth
    # compiled workload program (schedule shape; arrays live in the state)
    n_phases: int = 0
    schedule: str = "barrier"    # "barrier" | "window"
    window: int = 1              # lookahead depth for schedule="window"

    def __post_init__(self):
        check_pattern(self.pattern, engine=True)
        if self.pattern == "arrival" and self.process not in ARRIVAL_PATTERNS:
            raise ValueError(f"unknown arrival process {self.process!r}; "
                             f"expected one of {ARRIVAL_PATTERNS}")


# Donated row scatters for in-place device-table updates: the old table
# buffer is consumed and rewritten rather than double-buffered — at paper
# scale the mask tables are the largest device arrays, so the delta path
# must never hold two copies.
@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_rows(table, rows, vals):
    return table.at[rows].set(vals)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_rows_batch(table, rows, vals):
    return table.at[:, rows].set(vals[None])


class Simulator:
    def __init__(self, tables: RoutingTables, cfg: SimConfig,
                 failures=None):
        if cfg.backend not in BACKENDS:
            raise ValueError(f"unknown backend {cfg.backend!r}; "
                             f"expected one of {BACKENDS}")
        topo = tables.topo
        self.tables, self.cfg = tables, cfg
        # failure machinery is a *static* branch: with no schedule (or an
        # empty one) every step traces exactly as before — routing tables
        # are read-only arguments of the jitted loops and no live masks
        # ride in the state, so the parity goldens are bitwise-untouched.
        # With a schedule, the tables move into the state (``tbl_rows``
        # for the fused policies, ``tbl_min`` / ``tbl_dist`` otherwise,
        # + ``link_up`` / ``switch_up``) so ``update_tables`` can rewrite
        # them mid-run without recompiling.
        self.failures = failures
        self.has_failures = failures is not None and len(failures.events) > 0
        if failures is not None:
            failures.validate(topo)
        self.N = topo.n_switches
        self.P = topo.max_ports
        self.V = cfg.vcs
        self.Q = cfg.queue_depth
        self.QE = cfg.endpoint_queue
        self.n1 = topo.n_leaves
        self.d_leaf = topo.endpoints_per_leaf
        self.S = topo.n_endpoints
        self.NQ = self.N * self.P * self.V
        self.pool = cfg.pool or int(min(2_000_000, max(1 << 14, self.S * 6)))

        self.nbrs = jnp.asarray(topo.nbrs, jnp.int32)            # [N,P]
        self.nbr_port = jnp.asarray(topo.nbr_port, jnp.int32)    # [N,P]
        self.valid_port = self.nbrs >= 0
        self.nbrs0 = jnp.maximum(self.nbrs, 0)
        assert (tables.dist_leaf >= 0).all(), "disconnected topology"
        self.leaf_ids = jnp.asarray(topo.leaf_ids, jnp.int32)    # [N1]
        self.W = (self.P + 31) // 32
        self.fused = cfg.policy in FUSED_POLICIES
        if self.fused:
            # fused route rows [N1*N, K]: toward bits, away bits and the
            # int16 distance of one (leaf, switch) pair, so the full
            # Polarized classification against one leaf is one row gather
            self.K = route_row_words(self.P)
            self.route_rows = self._build_route_rows(tables)
            self.min_mask = self.dist = None
        else:
            # int16 distance table (UGAL's inject reads it at mixed
            # indices); all consumers use the values in comparisons / tiny
            # products, where int16 is exact
            self.dist = jnp.asarray(tables.dist_leaf, jnp.int16)  # [N1,N]
            # compact port bitmasks [N1*N, W]: one uint32-word gather + bit
            # test replaces a [P]-wide distance-row gather per requester.
            # Built by streaming leaf blocks — with blocked tables the
            # dense numpy arrays are never materialized on the host.
            self.min_mask = self._build_device_masks(tables)
            self.route_rows = None
        self._w_idx = jnp.asarray(np.arange(self.P) // 32, np.int32)
        self._b_idx = jnp.asarray(np.arange(self.P) % 32, np.uint32)

        # bit-packing bounds: p_sd packs two leaf ranks into 16 bits each,
        # p_bh keeps hops in the low byte (born slot above it); flat index
        # spaces (mask rows, queue buffers, pool) must fit int32 — audited
        # here so a 1M-endpoint spec fails loudly at construction instead
        # of silently wrapping gather indices at runtime
        assert self.n1 < (1 << 16), "leaf rank overflows the p_sd packing"
        assert cfg.max_hops < 255, "hop count overflows the p_bh packing"
        assert self.n1 * self.N < (1 << 31), \
            "mask-table row index overflows int32"
        assert self.NQ * max(self.Q, cfg.out_queue) < (1 << 31), \
            "flat queue-buffer index overflows int32"
        assert self.pool < (1 << 31), "pool index overflows int32"

        self._init_requester_geometry(topo)
        self._sharded_cache: dict = {}
        self._closed = False

    # The routing tables are the largest device arrays (~0.8 GB at 104,976
    # endpoints).  The jitted loops take them as (undonated) arguments and
    # trace the step on a copy of the simulator bound to those arguments,
    # so they never become constants of the compiled program, which would
    # carry them inside every executable and persistent-cache entry.
    _TABLE_ATTRS = ("min_mask", "dist", "route_rows")

    def _tables(self) -> dict:
        return {k: getattr(self, k) for k in self._TABLE_ATTRS}

    def _bound(self, tb: dict) -> "Simulator":
        """Shallow copy of ``self`` whose tables are the traced ``tb``."""
        sim = copy.copy(self)
        sim.__dict__.update(tb)
        return sim

    def _build_device_masks(self, tables: RoutingTables):
        """Device toward-bit table ``[N1*N, W]`` of the minimal policies,
        assembled from streamed leaf blocks
        (:meth:`RoutingTables.mask_blocks`).

        Works for both table layouts.  With ``mask_layout="blocked"`` the
        dense numpy arrays are never built: numpy peak is one
        ``[leaf_block, N, W]`` pair, and *retained* memory is the device
        table alone.  The assembly itself still peaks at ~2x the table
        while ``jnp.concatenate`` copies the collected blocks into the
        flat array — the blocked layout's durable win is retention, not
        the assembly transient.
        """
        mins = []
        for _lo, _hi, min_b, _away_b in tables.mask_blocks():
            mins.append(jnp.asarray(min_b.reshape(-1, self.W)))
        return mins[0] if len(mins) == 1 else jnp.concatenate(mins)

    def _build_route_rows(self, tables: RoutingTables):
        """Device fused route-row table ``[N1*N, K]``: streamed leaf blocks
        packed into one host array, which moves to the device once — the
        device never holds two copies of it."""
        n, k = self.N, self.K
        rows = np.empty((self.n1 * n, k), np.uint32)
        for lo, hi, min_b, away_b in tables.mask_blocks():
            rows[lo * n:hi * n] = pack_route_rows(
                min_b, away_b, tables.dist_leaf[lo:hi], self.P
            ).reshape(-1, k)
        return jnp.asarray(rows)

    def _init_requester_geometry(self, topo) -> None:
        """Static per-requester index tables for the crossbar hot path.

        Requester rows are ``[N*P network inputs] ++ [S endpoint NICs]``.
        Everything here depends only on the topology, so it is baked into
        the compiled step as constants instead of being recomputed from
        ``nbrs``/``nbr_port`` every sub-round.
        """
        N, P, V, S, d = self.N, self.P, self.V, self.S, self.d_leaf
        nbrs = np.asarray(topo.nbrs)
        nbr_port = np.asarray(topo.nbr_port)
        leaf_ids = np.asarray(topo.leaf_ids)

        cur_net = np.repeat(np.arange(N, dtype=np.int32), P)
        cur_ep = leaf_ids[np.arange(S, dtype=np.int32) // d]
        cur = np.concatenate([cur_net, cur_ep])                  # [NR]
        self.NR = NR = cur.shape[0]
        self.cur = jnp.asarray(cur)
        ports = np.arange(P, dtype=np.int32)
        # V-major occupancy layout: row (switch * V + vc) holds the [P]
        # occupancy vector every requester of that switch with that flight
        # VC needs, so the per-requester congestion lookup is a contiguous
        # row gather indexed by cur * V + next_vc — no [NR, P] index
        # matrices and no random-element gathers in the hot path.
        self._dq_perm = jnp.asarray(
            ((np.maximum(nbrs, 0) * P + np.maximum(nbr_port, 0))
             [:, None, :] * V
             + np.arange(V, dtype=np.int32)[None, :, None]
             ).reshape(-1).astype(np.int32))                     # [N*V*P]
        # UGAL source-switch occupancy (flat qlen index, VC 0)
        if self.cfg.policy == "ugal":
            sw = leaf_ids[np.arange(S, dtype=np.int32) // d]
            self._ugal_occ_idx = jnp.asarray(
                (np.maximum(nbrs, 0)[sw] * P + nbr_port[sw]) * V)  # [S,P]
        # dense per-switch requester layout (pallas kernel + the scatter-free
        # grant inversion).  Row r of switch n is net in-port r (r < P) or
        # NIC slot r - P (leaf switches only); ``row_of`` maps flat
        # requester index -> dense row.
        self.R_max = P + d
        net_rows = cur_net.astype(np.int64) * self.R_max + np.tile(
            ports, N)
        ep_rows = (cur_ep.astype(np.int64) * self.R_max + P
                   + np.arange(S, dtype=np.int64) % d)
        self._row_of = jnp.asarray(
            np.concatenate([net_rows, ep_rows]).astype(np.int32))
        self._lo = jnp.arange(NR, dtype=jnp.int32)
        # static flat -> dense-row gather (the inverse of row_of, with a
        # harmless duplicate fill for rows no requester occupies): lets the
        # XLA backend run the same dense per-switch segmented reduction the
        # Pallas kernel uses, without any scatter
        inv = np.zeros(N * self.R_max, np.int64)
        inv[np.concatenate([net_rows, ep_rows])] = np.arange(NR)
        self._dense_src = jnp.asarray(inv.astype(np.int32))      # [N*R_max]
        occupied = np.zeros(N * self.R_max, bool)
        occupied[np.concatenate([net_rows, ep_rows])] = True
        self._dense_valid = jnp.asarray(occupied.reshape(N, self.R_max))
        # link reversal: the input port (n', p') is fed by exactly one
        # output port — static, so receives invert sends with a gather
        rev = (np.maximum(nbrs, 0) * P + np.maximum(nbr_port, 0))
        self._rev_idx = jnp.asarray(rev.reshape(-1).astype(np.int32))

    # ------------------------------------------------------------------ #
    # lifetime: compiled step functions are jit-cached with ``self`` as a
    # static argument, so long-lived suites (~25 instances) accumulate
    # executables until the host OOMs.  ``close()`` makes the teardown that
    # callers used to do by hand (``del sim; jax.clear_caches()``) explicit
    # and idempotent; the context-manager form scopes it.
    # ------------------------------------------------------------------ #
    def close(self, clear: bool = True) -> None:
        """Mark the simulator dead and (by default) clear jax's jit caches.

        jax has no per-instance executable eviction, so ``clear=True`` is a
        process-global ``jax.clear_caches()`` — other live simulators will
        recompile on next use.  Batch teardowns (``SimulatorCache.close``)
        pass ``clear=False`` per instance and clear once at the end.
        """
        if self._closed:
            return
        self._closed = True
        self._sharded_cache.clear()
        if clear:
            jax.clear_caches()

    @property
    def closed(self) -> bool:
        return self._closed

    def __enter__(self) -> "Simulator":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    # ------------------------------------------------------------------ #
    def init_state(self, traffic: Traffic, seed_arrays: dict) -> dict:
        f32, i32 = jnp.float32, jnp.int32
        Z = lambda *s: jnp.zeros(s, i32)
        st = {
            "qbuf": jnp.full((self.NQ, self.Q), -1, i32),
            "qhead": Z(self.NQ), "qlen": Z(self.NQ),
            "oq_buf": jnp.full((self.NQ, self.cfg.out_queue), -1, i32),
            "oq_head": Z(self.NQ), "oq_len": Z(self.NQ),
            "eq_buf": jnp.full((self.S, self.QE), -1, i32),
            "eq_head": Z(self.S), "eq_len": Z(self.S),
            # packet pool + ring-buffer free-list (all pool slots free);
            # pops at inject are O(S), pushes at eject O(NR) — no per-slot
            # nonzero scan over the pool.  There is no free bitmap in the
            # hot path: free = the fl_buf ring window (see free_ids()).
            # Per-packet attributes are bit-packed to halve the pool
            # scatters/gathers: p_sd = src_leaf << 16 | dst_leaf,
            # p_bh = born_slot << 8 | hops.
            "fl_buf": jnp.arange(self.pool, dtype=i32),
            "fl_head": Z(), "fl_len": jnp.asarray(self.pool, i32),
            "p_sd": Z(self.pool),
            "p_mid": jnp.full(self.pool, -1, i32),
            "p_bh": Z(self.pool),
            # endpoint message program
            "msg_rem": Z(self.S), "msg_dst": Z(self.S), "prog": Z(self.S),
            # stats
            "ejected": Z(), "created": Z(), "hop_sum": Z(),
            "pool_stall": Z(),
            "lat_hist": Z(self.cfg.hist_bins),
            "slot": Z(),
            "key": jax.random.PRNGKey(self.cfg.seed),
        }
        if self.has_failures:
            # routing tables ride in the (donated) state so update_tables
            # can rewrite rows mid-run.  jnp.array copies — never aliases
            # of the closure constants, which would be consumed with the
            # first donated chunk.
            if self.fused:
                st["tbl_rows"] = jnp.array(self.route_rows)
            else:
                st["tbl_min"] = jnp.array(self.min_mask)
                st["tbl_dist"] = jnp.array(self.dist.reshape(-1))
            st["link_up"] = jnp.array(self.valid_port.reshape(-1))
            st["switch_up"] = jnp.ones(self.N, bool)
            st["fail_drop"] = Z()
        st.update({k: jnp.asarray(v) for k, v in seed_arrays.items()})
        return st

    # ------------------------------------------------------------------ #
    def _port_bits(self, table, t_lr, cur):
        """[len(t_lr), P] bool port mask from a packed table: one
        uint32-word gather per requester instead of a [P] distance row.
        Invalid ports are already zero in the packed words."""
        words = table[t_lr * self.N + cur]                       # [.,W]
        return ((words[:, self._w_idx] >> self._b_idx) & 1).astype(bool)

    def _row_bits(self, rows, start: int):
        """[len(rows), P] bool: bits ``start .. start+P-1`` of fused route
        rows, decoded with static word slices and shifts."""
        parts = []
        for w in range(start // 32, (start + self.P - 1) // 32 + 1):
            lo, hi = max(start, 32 * w), min(start + self.P, 32 * (w + 1))
            shifts = np.arange(lo - 32 * w, hi - 32 * w, dtype=np.uint32)
            parts.append((rows[:, w:w + 1] >> shifts) & 1)
        return jnp.concatenate(parts, axis=1).astype(bool)

    def _row_fields(self, table, leaf_rank, cur):
        """``(toward [.,P], away [.,P], d [.] int16)`` of leaf ``leaf_rank``
        at switch ``cur``: one fused route-row gather per requester."""
        rows = table[leaf_rank * self.N + cur]                    # [.,K]
        d = jax.lax.bitcast_convert_type(rows[:, -1], jnp.int32) >> 16
        return (self._row_bits(rows, 0), self._row_bits(rows, self.P),
                d.astype(jnp.int16))

    # ------------------------------------------------------------------ #
    def _inject(self, st, key, traffic: Traffic):
        """Start messages + push one packet per eligible endpoint."""
        S, d = self.S, self.d_leaf
        e = jnp.arange(S, dtype=jnp.int32)
        k1, k2, k3, k4 = jax.random.split(key, 4)

        idle = st["msg_rem"] == 0
        pat = traffic.pattern
        burst_new = None
        if pat in BERNOULLI_PATTERNS:
            if pat == "bursty":
                # two-state Markov (on-off) modulation: in-burst injection
                # probability is ``burst_load``, mean burst length is
                # ``burst_len`` slots, and the idle->burst rate is set so
                # the long-run offered load equals ``load``
                rho = min(traffic.load / traffic.burst_load, 0.999)
                p_off = 1.0 / max(traffic.burst_len, 1.0)
                p_on = min(1.0, p_off * rho / max(1.0 - rho, 1e-9))
                ka, kb = jax.random.split(k3)
                was_on = st["burst"] > 0
                on = jnp.where(was_on,
                               jax.random.uniform(ka, (S,)) >= p_off,
                               jax.random.uniform(kb, (S,)) < p_on)
                burst_new = on.astype(jnp.int32)
                start = idle & on & (jax.random.uniform(k1, (S,)) <
                                     traffic.burst_load)
            else:
                start = idle & (jax.random.uniform(k1, (S,)) <
                                traffic.load / self._mean_msg(traffic))
            if pat in ("uniform", "mice_elephant", "bursty"):
                dst = jax.random.randint(k2, (S,), 0, S)
            elif pat == "rep":
                dst = st["perm"]
            elif pat == "rsp":
                dst = st["sigma"][e // d] * d + (e % d)
            elif pat == "bu":  # two halves exchange uniformly
                half = S // 2
                lower = e < half
                r = jax.random.randint(k2, (S,), 0, half)
                dst = jnp.where(lower, half + r, r % half)
            elif pat == "tornado":
                # adversarial leaf-level half-rotation: every leaf targets
                # the leaf halfway around the leaf ranking (same slot
                # offset within the leaf) — zero locality, maximal
                # pressure on the non-minimal path diversity
                dst = ((e // d + self.n1 // 2) % self.n1) * d + e % d
            elif pat == "shift":
                dst = (e + traffic.shift) % S
            else:  # hotspot — incast a fraction onto a few hot endpoints
                kh, ki = jax.random.split(k3)
                hot = jax.random.uniform(kh, (S,)) < traffic.hot_frac
                dst = jnp.where(
                    hot, jax.random.randint(ki, (S,), 0, traffic.hot_count),
                    jax.random.randint(k2, (S,), 0, S))
            size = jnp.ones((S,), jnp.int32)
            if pat == "mice_elephant":
                size = jnp.where(jax.random.uniform(k3, (S,)) < traffic.elephant_frac,
                                 traffic.elephant_size, 1)
        elif pat == "arrival":
            # open-loop serving source: generate at most one request batch
            # per endpoint per slot, queue it in the per-endpoint FIFO
            # (dropping on overflow — the source never back-pressures),
            # then let idle endpoints pop their head batch.  All of this is
            # behind a static Python branch: existing patterns trace
            # exactly as before (parity goldens stay bitwise).
            proc = traffic.process
            D = traffic.arr_depth
            u_arr = jax.random.uniform(k1, (S,))
            if proc == "poisson":
                arrive = u_arr < traffic.load
                batch = jnp.ones((S,), jnp.int32)
            elif proc == "pareto":
                # bounded-Pareto batch sizes via inverse CDF; the arrival
                # probability is divided by the exact discrete batch mean
                # so the long-run offered load calibrates to ``load``
                alpha = traffic.pareto_alpha
                cap = traffic.pareto_cap
                arrive = u_arr < traffic.load / bounded_pareto_mean(alpha,
                                                                    cap)
                if cap <= 1:
                    batch = jnp.ones((S,), jnp.int32)
                else:
                    u = jax.random.uniform(k3, (S,))
                    x = (1.0 - u * (1.0 - float(cap) ** -alpha)) \
                        ** (-1.0 / alpha)
                    batch = jnp.clip(jnp.floor(x), 1, cap).astype(jnp.int32)
            else:  # diurnal — sinusoidal rate modulation around ``load``
                w = 2.0 * np.pi / traffic.diurnal_period
                rate = traffic.load * (
                    1.0 + traffic.diurnal_amp
                    * jnp.sin(w * st["slot"].astype(jnp.float32)))
                arrive = u_arr < rate
                batch = jnp.ones((S,), jnp.int32)
            room = st["arr_len"] < D
            push = arrive & room
            tail = (st["arr_head"] + st["arr_len"]) % D
            hot = push[:, None] & (jnp.arange(D, dtype=jnp.int32)[None, :]
                                   == tail[:, None])
            arr_times = jnp.where(hot, st["slot"], st["arr_times"])
            arr_sizes = jnp.where(hot, batch[:, None], st["arr_sizes"])
            arr_len = st["arr_len"] + push.astype(jnp.int32)
            # pop: idle endpoints start serving their head batch (a batch
            # arriving this slot may pop immediately — zero source
            # queueing keeps the latency-1 floor of the local fast path)
            start = idle & (arr_len > 0)
            headi = e * D + st["arr_head"]
            size = jnp.maximum(arr_sizes.reshape(-1)[headi], 1)
            birth = arr_times.reshape(-1)[headi]
            dst = jax.random.randint(k2, (S,), 0, S)
            arrival_updates = {
                "arr_times": arr_times,
                "arr_sizes": arr_sizes,
                "arr_head": jnp.where(start, (st["arr_head"] + 1) % D,
                                      st["arr_head"]),
                "arr_len": arr_len - start.astype(jnp.int32),
                "arrived": st["arrived"]
                + jnp.where(push, batch, 0).sum(dtype=jnp.int32),
                "arr_drop": st["arr_drop"]
                + jnp.where(arrive & ~room, batch, 0).sum(dtype=jnp.int32),
                "msg_birth": jnp.where(start, birth, st["msg_birth"]),
            }
        elif pat == "all2all":
            start = idle & (st["prog"] < traffic.rounds)
            dst = (e + st["prog"] + 1) % S
            size = jnp.ones((S,), jnp.int32)
        elif pat == "phase":
            start = idle & (st["prog"] < 1)
            dst = st["partner"]
            size = jnp.full((S,), traffic.phase_packets, jnp.int32)
        elif pat == "program":
            NP = traffic.n_phases
            if traffic.schedule == "window":
                # windowed/pipelined rounds: st["prog"] is the per-endpoint
                # phase pointer; an endpoint may start its phase-p message
                # once p is within ``window`` of the globally-completed
                # phase count
                ncomp = jnp.sum((st["phase_done"] >= 0).astype(jnp.int32))
                pe = st["prog"]
                start = idle & (pe < jnp.minimum(ncomp + traffic.window, NP))
                idx = jnp.clip(pe, 0, NP - 1) * S + e
                dst = st["prog_partner"].reshape(-1)[idx]
                size = st["prog_packets"].reshape(-1)[idx]
            else:
                # barrier: one message per endpoint per phase, rows gathered
                # from the current phase of the compiled program — bitwise
                # the legacy "phase" inject while a phase is active
                ph = jnp.minimum(st["phase"], NP - 1)
                start = idle & (st["prog"] < 1) & (st["phase"] < NP)
                dst = st["prog_partner"][ph]
                size = st["prog_packets"][ph]
        else:
            raise ValueError(pat)

        msg_rem = jnp.where(start, size, st["msg_rem"])
        msg_dst = jnp.where(start, dst, st["msg_dst"])
        prog = st["prog"] + start.astype(jnp.int32)

        # one packet per endpoint with pending message + NIC room
        want = (msg_rem > 0) & (st["eq_len"] < self.QE)
        src_lr = e // d
        dst_lr = msg_dst // d
        local = src_lr == dst_lr
        # same-leaf fast path: delivered without entering the network.
        deliver_local = want & local
        want_net = want & ~local

        # O(S) free-list pop: requester with rank r takes the r-th entry of
        # the ring buffer; requesters past the free count get the -1
        # sentinel (pool_stall) rather than an aliased packet id.
        rank = jnp.cumsum(want_net.astype(jnp.int32)) - 1
        ok = want_net & (rank < st["fl_len"])
        slot_idx = (st["fl_head"] + jnp.maximum(rank, 0)) % self.pool
        pid = jnp.where(ok, st["fl_buf"][slot_idx], -1)
        n_pop = ok.sum(dtype=jnp.int32)

        # UGAL/Valiant: sample intermediate leaf & (UGAL) compare queue depths
        mid = jnp.full((S,), -1, jnp.int32)
        if self.cfg.policy in ("ugal", "valiant"):
            mid_lr = jax.random.randint(k4, (S,), 0, self.n1)
            if self.cfg.policy == "ugal":
                sw = self.leaf_ids[src_lr]
                occ0 = st["qlen"][self._ugal_occ_idx]             # [S,P]
                if self.has_failures:
                    # state-resident tables + live-port gating; float32
                    # products because UNREACHABLE distances would wrap
                    # the int32 q*d score
                    live_sw = st["link_up"].reshape(self.N, self.P)[sw]
                    dflat = st["tbl_dist"]
                    def best(t_lr):
                        m = self._port_bits(st["tbl_min"], t_lr, sw) & live_sw
                        return jnp.min(jnp.where(m, occ0, 1 << 20), axis=1)
                    q_min = best(dst_lr)
                    q_val = best(mid_lr)
                    d_min = dflat[dst_lr * self.N + sw]
                    d_val = (dflat[mid_lr * self.N + sw]
                             + dflat[dst_lr * self.N + self.leaf_ids[mid_lr]])
                    take_val = (q_min.astype(jnp.float32) * d_min
                                > q_val.astype(jnp.float32) * d_val)
                else:
                    def best(t_lr):
                        m = self._port_bits(self.min_mask, t_lr, sw)
                        return jnp.min(jnp.where(m, occ0, 1 << 20), axis=1)
                    q_min = best(dst_lr)
                    q_val = best(mid_lr)
                    d_min = self.dist[dst_lr, sw]
                    d_val = self.dist[mid_lr, sw] + self.dist[dst_lr, self.leaf_ids[mid_lr]]
                    take_val = q_min * d_min > q_val * d_val
                mid = jnp.where(take_val, mid_lr, -1)
            else:
                mid = mid_lr

        # sentinel index == pool size -> dropped writes for non-injectors
        widx = jnp.where(ok, jnp.maximum(pid, 0), self.pool)
        st = dict(st)
        if burst_new is not None:
            st["burst"] = burst_new
        if pat == "arrival":
            st.update(arrival_updates)
        st["fl_head"] = (st["fl_head"] + n_pop) % self.pool
        st["fl_len"] = st["fl_len"] - n_pop
        st["p_sd"] = st["p_sd"].at[widx].set((src_lr << 16) | dst_lr,
                                             mode="drop")
        if self.cfg.policy in ("ugal", "valiant"):
            st["p_mid"] = st["p_mid"].at[widx].set(mid, mode="drop")
        # arrival packets are born at their batch's *arrival* slot, so
        # source queueing shows up in the latency histogram
        born = st["msg_birth"] if pat == "arrival" else st["slot"]
        st["p_bh"] = st["p_bh"].at[widx].set(born << 8, mode="drop")
        # push into NIC queue (dense one-hot write — one row per endpoint)
        pos = (st["eq_head"] + st["eq_len"]) % self.QE
        slot_hot = ok[:, None] & (jnp.arange(self.QE, dtype=jnp.int32)[None, :]
                                  == pos[:, None])
        st["eq_buf"] = jnp.where(slot_hot, jnp.maximum(pid, 0)[:, None],
                                 st["eq_buf"])
        st["eq_len"] = st["eq_len"] + ok.astype(jnp.int32)

        consumed = ok | deliver_local
        st["msg_rem"] = msg_rem - consumed.astype(jnp.int32)
        st["msg_dst"] = msg_dst
        st["prog"] = prog
        n_local = deliver_local.sum(dtype=jnp.int32)
        st["created"] = st["created"] + ok.sum(dtype=jnp.int32) + n_local
        st["ejected"] = st["ejected"] + n_local
        st["pool_stall"] = st["pool_stall"] + (want_net & ~ok).sum(dtype=jnp.int32)
        if pat == "arrival":
            # local fast-path deliveries also measure from the batch's
            # arrival slot, not the fixed 1-slot bin
            lat_loc = jnp.clip(st["slot"] - st["msg_birth"] + 1, 0,
                               self.cfg.hist_bins - 1)
            st["lat_hist"] = st["lat_hist"].at[
                jnp.where(deliver_local, lat_loc, 0)].add(
                jnp.where(deliver_local, 1, 0))
        else:
            st["lat_hist"] = st["lat_hist"].at[1].add(n_local)
        return st

    def _mean_msg(self, t: Traffic) -> float:
        if t.pattern == "mice_elephant":
            return (1 - t.elephant_frac) * 1.0 + t.elephant_frac * t.elephant_size
        return 1.0

    # ------------------------------------------------------------------ #
    def _crossbar_round(self, st, key, ep_active: bool):
        """One crossbar sub-round: VC pre-arbitration, routing, output
        arbitration, input-queue -> output-queue moves, ejections."""
        N, P, V, Q, S = self.N, self.P, self.V, self.Q, self.S
        OQ = self.cfg.out_queue
        k_vc, k_tie, k_arb = jax.random.split(key, 3)
        pallas = self.cfg.backend == "pallas"

        qlen3 = st["qlen"].reshape(N, P, V)
        # ---- VC pre-arbitration: one candidate VC per (switch, in-port) ----
        with jax.named_scope("vc_prearb"):
            vc_rand = jax.random.uniform(k_vc, (N, P, V))
            if pallas:
                from ..kernels.switch_arb.ops import vc_prearb_op
                vc_sel, has_pkt = vc_prearb_op(qlen3, vc_rand)
            else:
                vc_prio = jnp.where(qlen3 > 0, vc_rand, -1.0)
                vc_sel = jnp.argmax(vc_prio, axis=2)                 # [N,P]
                # the selected VC holds a packet iff any VC does
                has_pkt = jnp.max(vc_prio, axis=2) >= 0.0

        with jax.named_scope("route"):
            q_idx = (jnp.arange(N * P, dtype=jnp.int32).reshape(N, P) * V
                     + vc_sel.astype(jnp.int32)).reshape(-1)           # [N*P]
            head = st["qbuf"].reshape(-1)[q_idx * Q + st["qhead"][q_idx]]
            net_pkt = jnp.where(has_pkt.reshape(-1), head, -1)

            # endpoint (NIC) heads — only in sub-round 0 (NIC link rate = 1/slot)
            ep_head = st["eq_buf"].reshape(-1)[
                jnp.arange(S, dtype=jnp.int32) * self.QE + st["eq_head"]]
            ep_pkt = jnp.where((st["eq_len"] > 0) & ep_active, ep_head, -1)

            # ---- unified requester table (static geometry from __init__) ----
            cur = self.cur                                             # [NR]
            pkt = jnp.concatenate([net_pkt, ep_pkt])
            NR = self.NR
            valid = pkt >= 0
            pkt0 = jnp.maximum(pkt, 0)

            bh = st["p_bh"][pkt0]
            hops = bh & 0xFF
            sd = st["p_sd"][pkt0]
            t_lr = sd & 0xFFFF
            # destination switch is a pure function of the destination leaf:
            # a cache-resident [N1] gather, not another pool-wide attribute
            eject = valid & (cur == self.leaf_ids[t_lr])
            route = valid & ~eject
            pol = self.cfg.policy
            hf = self.has_failures
            if hf:
                # live tables from the state; live_row gates every policy's
                # candidate set to live ports (dead switches contribute
                # all-dead rows, so their packets freeze until drop/restore)
                trows = st.get("tbl_rows")
                tmin = st.get("tbl_min")
                live_row = st["link_up"].reshape(N, P)[cur]            # [NR,P]
            else:
                trows = self.route_rows
                tmin = self.min_mask
                live_row = None
            if pol == "polarized":
                # full Polarized classification from toward/away bits alone:
                # Forward = away-from-s & toward-t, Expansion = away & away
                # (while d_cs < d_ct), Contraction = toward & toward (once
                # d_cs >= d_ct); d(n,t) for the hop budget is d(c,t)+away-toward
                s_lr = sd >> 16
                dn_t, up_t, d_ct = self._row_fields(trows, t_lr, cur)
                dn_s, up_s, d_cs = self._row_fields(trows, s_lr, cur)
                src_side = (d_cs < d_ct)[:, None]
                deroute = (up_s & up_t & src_side) | (dn_s & dn_t & ~src_side)
                d_nt = (d_ct[:, None] + up_t.astype(jnp.int16)
                        - dn_t.astype(jnp.int16))
                budget_ok = (hops[:, None] + 1 + d_nt) <= self.cfg.max_hops
                allowed = (up_s & dn_t) | (deroute & budget_ok)
                next_vc = jnp.minimum(hops // 2, V - 1)
            elif pol == "degraded":
                # FatPaths-style layered recovery: minimal toward ports while
                # any are live; when failures kill them all, fall back to live
                # away ports (one layer up, +2 hops round trip) within the hop
                # budget.  On a pristine fabric the fallback never fires, so
                # degraded == minimal_adaptive bit for bit.
                toward, away, d_ct = self._row_fields(trows, t_lr, cur)
                if hf:
                    toward = toward & live_row
                    away = away & live_row
                no_min = ~jnp.any(toward, axis=1)
                budget_ok = (hops[:, None] + 2 + d_ct[:, None]) <= self.cfg.max_hops
                fallback = no_min[:, None] & away & budget_ok
                deroute = fallback
                allowed = toward | fallback
                next_vc = jnp.minimum(hops // 2, V - 1)
            elif pol in ("minimal_adaptive", "ksp"):
                allowed = self._port_bits(tmin, t_lr, cur)
                deroute = jnp.zeros_like(allowed)
                next_vc = jnp.minimum(hops // 2, V - 1)
            elif pol in ("ugal", "valiant"):
                mid_lr = st["p_mid"][pkt0]
                tgt = jnp.where(mid_lr >= 0, mid_lr, t_lr)
                allowed = self._port_bits(tmin, tgt, cur)
                deroute = jnp.zeros_like(allowed)
                next_vc = jnp.minimum(hops, V - 1)
            else:
                raise ValueError(pol)
            if hf and pol != "degraded":   # degraded gated its layers above
                allowed = allowed & live_row

            # congestion signal: local output queue + downstream input queue for
            # the flight VC.  Credit = room in the local output queue.  Both
            # lookups are contiguous row gathers from the V-major layout
            # (row = switch * V + flight VC), built once per round.
            oq_v = st["oq_len"].reshape(N, P, V).transpose(0, 2, 1) \
                .reshape(N * V, P)
            qd_v = st["qlen"][self._dq_perm].reshape(N * V, P)
            occ_row = cur * V + next_vc                                # [NR]
            oq_occ = oq_v[occ_row]                                     # [NR,P]
            occ = oq_occ + qd_v[occ_row]
            credit = oq_occ < OQ
            mask = allowed & credit
            if pol == "ksp":        # random walk: score is the tiebreak alone
                occ = jnp.zeros_like(occ)
                deroute = jnp.zeros_like(deroute)

        with jax.named_scope("out_arb"):
            tie = jax.random.uniform(k_tie, (NR, P))
            rnd = jax.random.randint(k_arb, (NR,), 0, 1 << 8, dtype=jnp.int32)
            if pallas:
                # fused score-evaluation + segmented output arbitration kernel
                from ..kernels.switch_arb.ops import switch_arbitrate_flat
                port, win, seg = switch_arbitrate_flat(
                    occ, deroute, mask, tie, route, rnd, self._lo,
                    penalty=float(self.cfg.deroute_penalty),
                    row_of=self._row_of, n_switches=N, r_max=self.R_max)
            else:
                score = (occ.astype(jnp.float32)
                         + self.cfg.deroute_penalty * deroute + tie)
                score = jnp.where(mask, score, BIG)
                port = jnp.argmin(score, axis=1).astype(jnp.int32)
                can_move = route & (jnp.min(score, axis=1) < BIG)

                # ---- output arbitration: one grant per (switch, out-port) ----
                out_key = cur * P + port                               # [NR]
                # unique int32 priorities: 8 random high bits | requester index
                prio = (rnd << 23) | self._lo
                prio = jnp.where(can_move, prio, -1)
                # dense per-switch segmented max — the same scatter-free
                # reduction the Pallas kernel runs (static row gathers; rows
                # with no requester carry priority -1)
                prio_d = jnp.where(self._dense_valid,
                                   prio[self._dense_src].reshape(N, self.R_max),
                                   -1)
                port_d = port[self._dense_src].reshape(N, self.R_max)
                hot = ((port_d[:, :, None]
                        == jnp.arange(P, dtype=jnp.int32))
                       & (prio_d >= 0)[:, :, None])                    # [N,R,P]
                seg = jnp.max(jnp.where(hot, prio_d[:, :, None], -1),
                              axis=1).reshape(-1)                      # [N*P]
                win = can_move & (seg[out_key] == prio)

        # ---- moves: input queue -> output queue ----
        with jax.named_scope("moves"):
            # XLA CPU scatters serialize element by element, so the queue
            # updates are phrased as gathers + dense one-hot selects instead:
            # the winning priority word per output port *is* the inverted grant
            # (its low 23 bits are the unique flat requester index).
            exist = seg >= 0                                           # [N*P]
            wlo = jnp.where(exist, seg & ((1 << 23) - 1), 0)
            win_pkt = pkt0[wlo]                                        # [N*P]
            win_vc = next_vc[wlo]
            v_ids = jnp.arange(V, dtype=jnp.int32)
            push = (exist[:, None] & (win_vc[:, None] == v_ids)).reshape(-1)
            pos = (st["oq_head"] + st["oq_len"]) % OQ                  # [NQ]
            slot_hot = push[:, None] & (jnp.arange(OQ, dtype=jnp.int32)[None, :]
                                        == pos[:, None])
            win_pkt_q = jnp.broadcast_to(win_pkt[:, None],
                                         (N * P, V)).reshape(-1)       # [NQ]
            oq_buf = jnp.where(slot_hot, win_pkt_q[:, None], st["oq_buf"])
            oq_len = st["oq_len"] + push.astype(jnp.int32)

            # pops: winners + ejectors leave their input queues (each
            # (switch, in-port) pops at most its one pre-arbitrated VC — dense)
            leave = win | eject
            net_leave = leave[: N * P]
            pop = (net_leave[:, None]
                   & (vc_sel.reshape(-1).astype(jnp.int32)[:, None] == v_ids)
                   ).reshape(-1).astype(jnp.int32)                     # [NQ]
            qhead = (st["qhead"] + pop) % Q
            qlen = st["qlen"] - pop
            ep_leave = leave[N * P:]
            eq_head = (st["eq_head"] + ep_leave.astype(jnp.int32)) % self.QE
            eq_len = st["eq_len"] - ep_leave.astype(jnp.int32)

            # ejections: free pool (O(N*P) free-list push), record stats.  Only
            # network input ports can eject (same-leaf traffic never enters the
            # network), so the pool scatters index the net rows alone.
            ej_n = eject[: N * P]
            pkt_n = pkt0[: N * P]
            erank = jnp.cumsum(ej_n.astype(jnp.int32)) - 1
            fpos = (st["fl_head"] + st["fl_len"] + jnp.maximum(erank, 0)) % self.pool
            fl_buf = st["fl_buf"].at[jnp.where(ej_n, fpos, self.pool)].set(
                pkt_n, mode="drop")
            fl_len = st["fl_len"] + ej_n.sum(dtype=jnp.int32)
            lat = jnp.clip(st["slot"] - (bh[: N * P] >> 8) + 1, 0,
                           self.cfg.hist_bins - 1)
            lat_hist = st["lat_hist"].at[jnp.where(ej_n, lat, 0)].add(
                jnp.where(ej_n, 1, 0))

            st = dict(st)
            st["oq_buf"] = oq_buf.reshape(self.NQ, OQ)
            st["oq_len"] = oq_len
            st["qhead"], st["qlen"] = qhead, qlen
            st["eq_head"], st["eq_len"] = eq_head, eq_len
            st["fl_buf"], st["fl_len"] = fl_buf, fl_len
            st["lat_hist"] = lat_hist
            st["ejected"] = st["ejected"] + eject.sum(dtype=jnp.int32)
            st["hop_sum"] = st["hop_sum"] + jnp.where(eject, hops, 0).sum(
                dtype=jnp.int32)
        return st

    def _link_phase(self, st, key):
        """Move one packet per link: output-queue head -> downstream input
        queue (credit-checked), incrementing hop counts and assigning the
        packet to the downstream switch."""
        N, P, V, Q = self.N, self.P, self.V, self.Q
        OQ = self.cfg.out_queue
        # pick one non-empty output VC per (switch, port) with downstream room
        oq_len3 = st["oq_len"].reshape(N, P, V)
        np_idx = jnp.arange(N * P, dtype=jnp.int32)
        sw = np_idx // P
        pt = np_idx % P
        nb = self.nbrs0[sw, pt]                                     # [N*P]
        nbp = self.nbr_port[sw, pt]
        link_ok = self.valid_port[sw, pt]
        if self.has_failures:
            link_ok = link_ok & st["link_up"]
        # downstream input queue per VC
        dq = (nb[:, None] * P + nbp[:, None]) * V + jnp.arange(V, dtype=jnp.int32)
        room = st["qlen"][dq] < Q                                   # [N*P,V]
        nonempty = oq_len3.reshape(N * P, V) > 0
        cand = nonempty & room & link_ok[:, None]
        prio = jnp.where(cand, jax.random.uniform(key, (N * P, V)), -1.0)
        vcs = jnp.argmax(prio, axis=1).astype(jnp.int32)
        send = jnp.take_along_axis(cand, vcs[:, None], 1)[:, 0]

        src_q = np_idx * V + vcs
        pkt = st["oq_buf"].reshape(-1)[src_q * OQ + st["oq_head"][src_q]]
        pkt0 = jnp.maximum(pkt, 0)

        # scatter-free queue updates: each (switch, port) pops at most one
        # VC (dense one-hot), and each *input* port receives from exactly
        # one static upstream output port, so receives are a gather through
        # the link-reversal map instead of a scatter through ``dq``.
        v_ids = jnp.arange(V, dtype=jnp.int32)
        pop = (send[:, None] & (vcs[:, None] == v_ids)
               ).reshape(-1).astype(jnp.int32)                      # [NQ]
        oq_head = (st["oq_head"] + pop) % OQ
        oq_len = st["oq_len"] - pop
        recv = send[self._rev_idx] & self.valid_port.reshape(-1)    # [N*P]
        recv_vc = vcs[self._rev_idx]
        recv_pkt = pkt0[self._rev_idx]
        push = (recv[:, None] & (recv_vc[:, None] == v_ids)).reshape(-1)
        qpos = (st["qhead"] + st["qlen"]) % Q                       # [NQ]
        slot_hot = push[:, None] & (jnp.arange(Q, dtype=jnp.int32)[None, :]
                                    == qpos[:, None])
        recv_pkt_q = jnp.broadcast_to(recv_pkt[:, None],
                                      (N * P, V)).reshape(-1)
        qbuf = jnp.where(slot_hot, recv_pkt_q[:, None], st["qbuf"])
        qlen = st["qlen"] + push.astype(jnp.int32)

        # hop increment on the packed born|hops word (hops are the low byte)
        p_bh = st["p_bh"].at[jnp.where(send, pkt0, self.pool)].add(
            1, mode="drop")
        # clear UGAL/Valiant intermediate when the packet reaches it (the
        # other policies never set p_mid, so they skip the bookkeeping)
        if self.cfg.policy in ("ugal", "valiant"):
            mid_lr = st["p_mid"][pkt0]
            reached_mid = send & (mid_lr >= 0) & (
                nb == self.leaf_ids[jnp.maximum(mid_lr, 0)])
            p_mid = st["p_mid"].at[jnp.where(reached_mid, pkt0, self.pool)
                                   ].set(-1, mode="drop")
        else:
            p_mid = st["p_mid"]

        st = dict(st)
        st["qbuf"] = qbuf
        st["qlen"] = qlen
        st["oq_head"], st["oq_len"] = oq_head, oq_len
        st["p_bh"], st["p_mid"] = p_bh, p_mid
        return st

    def _step(self, st, traffic: Traffic, chunk=None, max_slots=None):
        key, k_inj, k_link, *k_xb = jax.random.split(
            st["key"], 3 + self.cfg.speedup)
        st = dict(st)
        st["key"] = key
        # phase names are HLO metadata (op_name), read by the benchmark's
        # per-phase device time; they change no computation
        with jax.named_scope("inject"):
            st = self._inject(st, k_inj, traffic)
        for r in range(self.cfg.speedup):
            st = self._crossbar_round(st, k_xb[r], ep_active=True)
        with jax.named_scope("link"):
            st = self._link_phase(st, k_link)
        st["slot"] = st["slot"] + 1
        if traffic.pattern == "program":
            with jax.named_scope("program"):
                st = self._advance_program(st, traffic, chunk, max_slots)
        return st

    # ------------------------------------------------------------------ #
    # on-device phase scheduler for compiled workload programs
    # ------------------------------------------------------------------ #
    def _advance_program(self, st, traffic: Traffic, chunk, max_slots):
        """Per-slot phase bookkeeping for ``Traffic("program")``.

        ``barrier``: when the running phase's ejection target is met (or
        its chunk-granular ``max_slots`` budget expires), record the exact
        completion slot in ``phase_done``, bump ``phase``, and reset the
        transient state (queues' heads/lens, free-list, PRNG key, slot,
        per-endpoint message program) to what a fresh ``make_state`` would
        hold — so every phase is bitwise-identical to a standalone
        host-loop ``run_completion`` and ``phase_done`` holds per-phase
        durations.

        ``window``: no resets; ejections are cumulative, and phase ``p``
        completes once total deliveries reach ``expected_cum[p]``
        (``phase_done`` holds cumulative completion slots).
        """
        NP = traffic.n_phases
        pids = jnp.arange(NP, dtype=jnp.int32)
        st = dict(st)
        if traffic.schedule == "window":
            newly = (st["phase_done"] < 0) & (
                st["ejected"] >= st["prog_expected_cum"])
            st["phase_done"] = jnp.where(newly, st["slot"], st["phase_done"])
            st["phase_ok"] = st["phase_ok"] | newly
            st["phase"] = jnp.sum((st["phase_done"] >= 0).astype(jnp.int32))
            return st

        ph = st["phase"]
        active = ph < NP
        exp = st["prog_expected"][jnp.minimum(ph, NP - 1)]
        natural = active & (st["ejected"] >= exp)
        if max_slots is not None:
            # mirror the host loop's timeout semantics: it only notices a
            # stuck phase at a chunk boundary past max_slots, and records
            # that chunk-granular slot
            budget_gone = st["slot"] >= max_slots
            if chunk is not None:
                budget_gone &= st["slot"] % chunk == 0
            forced = active & budget_gone & ~natural
            crossed = natural | forced
        else:
            crossed = natural
        hot = (pids == ph) & crossed
        st["phase_done"] = jnp.where(hot, st["slot"], st["phase_done"])
        st["phase_ok"] = st["phase_ok"] | (hot & natural)
        st["phase"] = ph + crossed.astype(jnp.int32)
        # fresh-state reset: only what the next phase can observe — queue
        # buffers keep stale ids (unreachable at length 0) and pool
        # attributes keep stale packets (unreachable once the free-list is
        # re-initialized), exactly as behaviour-neutral as in a fresh state
        zero = lambda k: jnp.where(crossed, 0, st[k])
        st["slot"] = zero("slot")
        st["ejected"] = zero("ejected")
        st["prog"] = zero("prog")
        st["msg_rem"] = zero("msg_rem")
        for k in ("qhead", "qlen", "oq_head", "oq_len", "eq_head", "eq_len",
                  "fl_head"):
            st[k] = zero(k)
        st["fl_buf"] = jnp.where(crossed,
                                 jnp.arange(self.pool, dtype=jnp.int32),
                                 st["fl_buf"])
        st["fl_len"] = jnp.where(crossed, self.pool, st["fl_len"])
        st["key"] = jnp.where(crossed, st["key0"], st["key"])
        return st

    # ------------------------------------------------------------------ #
    # ``donate_argnums=(1,)``: the state pytree is updated in place by the
    # runtime instead of double-buffering every array per chunk.  The input
    # dict is CONSUMED — callers must keep using the returned state.
    # ------------------------------------------------------------------ #
    @functools.partial(jax.jit, static_argnums=(0, 3, 4), donate_argnums=(1,))
    def _run_chunk_jit(self, st, tb, traffic: Traffic, n_slots: int):
        sim = self._bound(tb)

        def body(carry, _):
            return sim._step(carry, traffic), None
        st, _ = jax.lax.scan(body, st, None, length=n_slots)
        return st

    def run_chunk(self, st, traffic: Traffic, n_slots: int):
        """Advance ``n_slots`` slots.  ``st`` is donated (consumed)."""
        with _quiet_cpu_donation():
            return self._run_chunk_jit(st, self._tables(), traffic, n_slots)

    @functools.partial(jax.jit, static_argnums=(0, 3, 4), donate_argnums=(1,))
    def _run_chunk_batch_jit(self, st, tb, traffic: Traffic, n_slots: int):
        sim = self._bound(tb)

        def one(s):
            def body(carry, _):
                return sim._step(carry, traffic), None
            return jax.lax.scan(body, s, None, length=n_slots)[0]
        return jax.vmap(one)(st)

    def run_chunk_batch(self, st, traffic: Traffic, n_slots: int):
        """``run_chunk`` vmapped over a leading ``[R]`` replica axis.
        ``st`` is donated (consumed)."""
        with _quiet_cpu_donation():
            return self._run_chunk_batch_jit(st, self._tables(), traffic,
                                             n_slots)

    # ------------------------------------------------------------------ #
    # sharded execution (the repro.parallel.sharding simulator profile)
    # ------------------------------------------------------------------ #
    def batch_pspecs(self, st, replica_axis: str) -> dict:
        """Per-entry ``PartitionSpec``s sharding the leading replica dim.

        Replica-invariant program arrays (``_PROG_SHARED``, one device
        copy in a batched state) stay replicated; everything else shards
        dim 0 over ``replica_axis``.
        """
        from jax.sharding import PartitionSpec as P
        specs = {}
        for k, v in st.items():
            nd = jnp.asarray(v).ndim
            if nd == self._PROG_SHARED.get(k, -1):
                specs[k] = P(*([None] * nd))
            else:
                specs[k] = P(replica_axis, *([None] * (nd - 1)))
        return specs

    def _sharded_chunk_fn(self, traffic: Traffic, n_slots: int, mesh,
                          replica_axis: str, spec_items):
        """Compiled ``shard_map``-over-replicas chunk executable.

        Cached per instance on the static shape of the call (traffic,
        slot count, mesh, state layout) — NOT in a class-level lru_cache,
        which would pin ``self`` (and its multi-hundred-MB device mask
        tables at paper scale) past :meth:`close` for the life of the
        process.  ``close()`` drops the cache with the instance.
        """
        key = (traffic, n_slots, mesh, replica_axis, spec_items)
        cached = self._sharded_cache.get(key)
        if cached is not None:
            return cached
        from jax.sharding import PartitionSpec as P
        specs = dict(spec_items)
        # shared (replicated) entries ride the inner vmap unbatched
        axes = {k: 0 if (len(p) and p[0] == replica_axis) else None
                for k, p in specs.items()}

        def chunk(s, tb):
            sim = self._bound(tb)

            def body(carry, _):
                return sim._step(carry, traffic), None
            return jax.lax.scan(body, s, None, length=n_slots)[0]

        # tables are replicated on every device and unbatched
        local = jax.vmap(chunk, in_axes=(axes, None), out_axes=axes)
        shmapped = jax.shard_map(local, mesh=mesh, in_specs=(specs, P()),
                                 out_specs=specs, check_vma=False)
        fn = jax.jit(shmapped, donate_argnums=(0,))
        self._sharded_cache[key] = fn
        return fn

    def run_chunk_sharded(self, st, traffic: Traffic, n_slots: int,
                          sharder):
        """``run_chunk_batch`` with the replica axis split over the
        devices of ``sharder.mesh`` via ``jax.shard_map``.

        Replicas are fully independent, so each device steps its own
        ``R / n_devices`` slice with zero cross-device traffic and every
        replica is **bitwise identical** to the single-device
        ``run_chunk_batch`` result (locked by
        ``tests/test_sharded_engine.py``).  ``st`` is donated (consumed).
        ``sharder`` is a :class:`repro.parallel.sharding.Sharder` with the
        simulator profile (``Sharder.for_simulator()``); the replica count
        must divide evenly over the mesh's ``replica`` axis.
        """
        axis = sharder.rules.replica
        if axis is None:
            raise ValueError("sharder has no replica axis; build it with "
                             "Sharder.for_simulator()")
        n_dev = sharder.mesh.shape[axis]
        r = st["ejected"].shape[0] if st["ejected"].ndim else None
        if r is None:
            raise ValueError("run_chunk_sharded needs a batched state "
                             "(make_batch_state)")
        if r % n_dev:
            raise ValueError(f"{r} replicas do not divide over {n_dev} "
                             f"devices on mesh axis {axis!r}")
        specs = self.batch_pspecs(st, axis)
        fn = self._sharded_chunk_fn(traffic, n_slots, sharder.mesh, axis,
                                    tuple(sorted(specs.items())))
        with _quiet_cpu_donation():
            return fn(st, self._tables())

    def state_shardings(self, st, sharder) -> dict:
        """Per-entry :class:`NamedSharding` for the per-switch layout.

        Queue-major arrays (leading dim ``N*P*V`` — input/output queues)
        and endpoint-major arrays (leading dim ``S`` — NIC queues,
        message programs) shard dim 0 over the mesh's ``switch`` axis
        (endpoints are leaf-major, so an endpoint split is a switch
        split); pool-indexed and scalar entries are replicated, since
        packets cross switch shards at the link phase.  Dims that the
        device count does not divide fall back to replicated (the
        ``constrain_safe`` rule).
        """
        from jax.sharding import NamedSharding, PartitionSpec as P
        axis = sharder.rules.switch
        if axis is None:
            raise ValueError("sharder has no switch axis; build it with "
                             "Sharder.for_simulator(axis='switch')")
        n_dev = sharder.mesh.shape[axis]
        switch_major = {self.NQ, self.S}
        out = {}
        for k, v in st.items():
            arr = jnp.asarray(v)
            shard = (arr.ndim >= 1 and arr.shape[0] in switch_major
                     and arr.shape[0] % n_dev == 0)
            spec = (P(axis, *([None] * (arr.ndim - 1))) if shard
                    else P(*([None] * arr.ndim)))
            out[k] = NamedSharding(sharder.mesh, spec)
        return out

    def shard_state(self, st, sharder) -> dict:
        """Place a scalar state onto the ``switch``-axis layout.

        The jitted step functions then run under GSPMD partitioning —
        same computation, communication inserted where packets cross
        shards — so results stay bitwise-identical to the unsharded run.
        """
        shardings = self.state_shardings(st, sharder)
        return {k: jax.device_put(jnp.asarray(v), shardings[k])
                for k, v in st.items()}

    @functools.partial(jax.jit, static_argnums=(0, 3, 5, 6),
                       donate_argnums=(1,))
    def _completion_loop(self, st, tb, traffic: Traffic, expected,
                         chunk: int, max_slots: int):
        """Device-side completion detection: a ``lax.while_loop`` over
        ``chunk``-slot scans that stops once every replica has ejected
        ``expected`` packets (or ``max_slots`` elapsed).  ``done`` records
        the *exact* slot at which each replica's ejection counter crossed
        ``expected`` (-1 while still running) — completion resolution is one
        slot, not one chunk, and there are no per-chunk host syncs.

        Works on scalar state (0-d ``ejected``) and batched state alike:
        the step is vmapped when a replica axis is present.
        """
        batched = st["ejected"].ndim == 1
        sim = self._bound(tb)
        step = lambda s: sim._step(s, traffic)
        if batched:
            step = jax.vmap(step)
        expected = jnp.asarray(expected, jnp.int32)

        def slot_body(carry, _):
            s, done = carry
            s = step(s)
            newly = (s["ejected"] >= expected) & (done < 0)
            done = jnp.where(newly, s["slot"], done)
            return (s, done), None

        def chunk_body(carry):
            return jax.lax.scan(slot_body, carry, None, length=chunk)[0]

        def cond(carry):
            s, done = carry
            running = ~jnp.all(done >= 0)
            return running & (jnp.max(s["slot"]) < max_slots)

        done0 = jnp.full_like(st["ejected"], -1)
        return jax.lax.while_loop(cond, chunk_body, (st, done0))

    @functools.partial(jax.jit, static_argnums=(0, 4, 5, 6, 7, 8),
                       donate_argnums=(1, 2))
    def _completion_loop_bounded(self, st, done, tb, traffic: Traffic,
                                 expected, chunk: int, max_slots: int,
                                 budget: int):
        """:meth:`_completion_loop` with a chunk *budget*: runs at most
        ``budget`` chunk bodies, then returns control to the host — the
        checkpointable chunk boundary.  The chunk body is byte-for-byte
        the unbounded loop's, so a sequence of bounded segments (resumed
        from snapshots of ``(state, done)``) replays the uninterrupted
        ``_completion_loop`` bitwise.  ``done`` is carried explicitly so a
        resumed run keeps the exact completion slots already recorded.
        """
        batched = st["ejected"].ndim == 1
        sim = self._bound(tb)
        step = lambda s: sim._step(s, traffic)
        if batched:
            step = jax.vmap(step)
        expected = jnp.asarray(expected, jnp.int32)

        def slot_body(carry, _):
            s, done = carry
            s = step(s)
            newly = (s["ejected"] >= expected) & (done < 0)
            done = jnp.where(newly, s["slot"], done)
            return (s, done), None

        def chunk_body(carry):
            s, done, it = carry
            (s, done), _ = jax.lax.scan(slot_body, (s, done), None,
                                        length=chunk)
            return (s, done, it + 1)

        def cond(carry):
            s, done, it = carry
            running = ~jnp.all(done >= 0)
            return (running & (jnp.max(s["slot"]) < max_slots)
                    & (it < budget))

        st, done, _ = jax.lax.while_loop(
            cond, chunk_body, (st, done, jnp.zeros((), jnp.int32)))
        return st, done

    # ------------------------------------------------------------------ #
    # high-level drivers
    # ------------------------------------------------------------------ #
    @staticmethod
    def _check_arrival(traffic: Traffic) -> None:
        # one validator for spec layer and engine (repro.workloads.patterns)
        check_arrival(traffic.process, traffic.load,
                      pareto_alpha=traffic.pareto_alpha,
                      pareto_cap=traffic.pareto_cap,
                      diurnal_amp=traffic.diurnal_amp,
                      diurnal_period=traffic.diurnal_period,
                      arr_depth=traffic.arr_depth)

    def make_state(self, traffic: Traffic, seed: int = 0) -> dict:
        if self._closed:
            raise RuntimeError("Simulator is closed")
        if traffic.pattern == "shift" and traffic.shift % self.S == 0:
            raise ValueError(
                f"shift offset {traffic.shift} is 0 mod {self.S} endpoints "
                "(every message would be self-addressed)")
        if traffic.pattern == "tornado" and self.n1 < 2:
            raise ValueError("tornado needs at least 2 leaves")
        if traffic.pattern == "hotspot" and traffic.hot_count > self.S:
            raise ValueError(
                f"hot_count {traffic.hot_count} > endpoints {self.S} "
                "(out-of-range destinations would silently clamp)")
        if traffic.pattern == "bursty":
            if traffic.load > traffic.burst_load:
                raise ValueError(
                    f"bursty load {traffic.load} exceeds burst_load "
                    f"{traffic.burst_load}: the long-run offered load can "
                    "never exceed the in-burst intensity")
            duty_max = traffic.burst_len / (traffic.burst_len + 1.0)
            if traffic.load > traffic.burst_load * duty_max:
                raise ValueError(
                    f"bursty duty cycle {traffic.load / traffic.burst_load:.3f} "
                    f"is unreachable: with mean burst length "
                    f"{traffic.burst_len} the ON fraction tops out at "
                    f"{duty_max:.3f} (even at p_on = 1), so the long-run "
                    "offered load would silently undershoot `load` — "
                    "raise burst_len or burst_load")
        if traffic.pattern == "arrival":
            self._check_arrival(traffic)
        rng = np.random.default_rng(seed)
        seed_arrays = {}
        if traffic.pattern == "rep":
            seed_arrays["perm"] = rng.permutation(self.S).astype(np.int32)
        if traffic.pattern == "rsp":
            seed_arrays["sigma"] = rng.permutation(self.n1).astype(np.int32)
        if traffic.pattern == "bursty":
            seed_arrays["burst"] = np.zeros(self.S, np.int32)  # all OFF
        if traffic.pattern == "phase":
            seed_arrays["partner"] = np.zeros(self.S, np.int32)  # set by caller
        if traffic.pattern == "arrival":
            D = traffic.arr_depth
            seed_arrays["arr_times"] = np.zeros((self.S, D), np.int32)
            seed_arrays["arr_sizes"] = np.zeros((self.S, D), np.int32)
            seed_arrays["arr_head"] = np.zeros(self.S, np.int32)
            seed_arrays["arr_len"] = np.zeros(self.S, np.int32)
            seed_arrays["msg_birth"] = np.zeros(self.S, np.int32)
            seed_arrays["arrived"] = np.zeros((), np.int32)
            seed_arrays["arr_drop"] = np.zeros((), np.int32)
        st = self.init_state(traffic, seed_arrays)
        if seed:  # thread the run seed into the sim PRNG (seed=0: legacy key)
            # fold_in, not key arithmetic: PRNGKey(cfg.seed + (seed << 16))
            # collides distinct (cfg.seed, seed) pairs, e.g. (65536, 0) with
            # (0, 1)
            st["key"] = jax.random.fold_in(
                jax.random.PRNGKey(self.cfg.seed), seed)
        return st

    def make_batch_state(self, traffic: Traffic, seeds) -> dict:
        """Stack R independently-seeded states on a leading replica axis.

        Each replica's slice is exactly the state ``make_state(traffic, s)``
        would produce — seed-dependent traffic permutations (``rep``/``rsp``)
        and the PRNG stream both vary per replica — so a vmapped run is
        replica-for-replica identical to R scalar runs.
        """
        states = [self.make_state(traffic, seed=int(s)) for s in seeds]
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)

    @staticmethod
    def free_ids(st) -> np.ndarray:
        """Host-side view of the free packet ids (the fl_buf ring window)
        of a scalar state.  ``pool - fl_len`` packets are in flight."""
        buf = np.asarray(st["fl_buf"])
        head, n = int(st["fl_head"]), int(st["fl_len"])
        return buf[(head + np.arange(n)) % buf.shape[0]]

    @staticmethod
    def arrival_backlog(st) -> int:
        """Host-side sum of packets still queued in the arrival FIFOs of a
        scalar ``Traffic("arrival")`` state (the live ring windows).  With
        ``sum(msg_rem)`` (popped but not yet injected) this closes the
        open-loop conservation ledger:
        ``arrived == backlog + sum(msg_rem) + created``."""
        sizes = np.asarray(st["arr_sizes"])
        head = np.asarray(st["arr_head"])
        ln = np.asarray(st["arr_len"])
        D = sizes.shape[1]
        idx = (head[:, None] + np.arange(D)[None, :]) % D
        live = np.arange(D)[None, :] < ln[:, None]
        return int(np.take_along_axis(sizes, idx, 1)[live].sum())

    def _count_steps(self, slots: int) -> None:
        """Count ``slots`` stepped slots (replicas summed) and the
        routing-table rows their route phases gathered: 2 a requester and
        sub-round for ``polarized`` (source and target row), 1 for the
        rest.  Host arithmetic on static numbers, no device read."""
        tracing.count(SLOTS_STEPPED, slots)
        per = 2 if self.cfg.policy == "polarized" else 1
        tracing.count(ROUTE_ROWS, slots * self.cfg.speedup * self.NR * per)

    @staticmethod
    def _counter_snapshot(st) -> dict:
        # fresh device buffers (`x + 0`), not views: the source state is
        # about to be donated to the measurement chunk
        return {k: st[k] + 0 for k in ("ejected", "hop_sum", "pool_stall")}

    def run_throughput(self, traffic: Traffic, warm: int = 200,
                       measure: int = 400, seed: int = 0) -> dict:
        with tracing.span("runner.prepare"):
            st = self.make_state(traffic, seed)
        st = self.run_chunk(st, traffic, warm)
        base = self._counter_snapshot(st)
        st = self.run_chunk(st, traffic, measure)
        self._count_steps(warm + measure)
        # warm/measure deltas computed on device, fetched in ONE transfer
        # (the old path issued three blocking int() syncs per phase)
        m = jax.device_get({k: st[k] - base[k] for k in base}
                           | {"ejected_total": st["ejected"]})
        return {
            "throughput": int(m["ejected"]) / (self.S * measure),
            # steady-state window only: the cumulative ratio used to fold
            # warmup transients into the reported hop count
            "avg_hops": int(m["hop_sum"]) / max(int(m["ejected"]), 1),
            "ejected": int(m["ejected_total"]),
            "pool_stall": int(m["pool_stall"]),
            "state": st,
        }

    def run_throughput_batch(self, traffic: Traffic, seeds,
                             warm: int = 200, measure: int = 400,
                             sharder=None) -> dict:
        """Batched ``run_throughput``: one compiled executable, R replicas.

        Returns per-replica ``[R]`` arrays for every metric.  With a
        ``sharder`` (simulator profile, replica axis) the replica batch is
        split over the mesh devices via :meth:`run_chunk_sharded` — same
        per-replica results, bitwise.
        """
        if sharder is not None:
            chunk = lambda s, n: self.run_chunk_sharded(s, traffic, n,
                                                        sharder)
        else:
            chunk = lambda s, n: self.run_chunk_batch(s, traffic, n)
        with tracing.span("runner.prepare"):
            st = self.make_batch_state(traffic, seeds)
        st = chunk(st, warm)
        base = self._counter_snapshot(st)
        st = chunk(st, measure)
        self._count_steps((warm + measure) * len(seeds))
        m = jax.device_get({k: st[k] - base[k] for k in base}
                           | {"ejected_total": st["ejected"]})
        e, h = np.asarray(m["ejected"]), np.asarray(m["hop_sum"])
        return {
            "throughput": e / (self.S * measure),
            "avg_hops": h / np.maximum(e, 1),
            "ejected": np.asarray(m["ejected_total"]),
            "pool_stall": np.asarray(m["pool_stall"]),
            "state": st,
        }

    def run_latency(self, traffic: Traffic, warm: int = 200,
                    measure: int = 600, seed: int = 0) -> dict:
        with tracing.span("runner.prepare"):
            st = self.make_state(traffic, seed)
        st = self.run_chunk(st, traffic, warm)
        base = st["lat_hist"] + 0            # fresh buffer; st is donated
        st = self.run_chunk(st, traffic, measure)
        self._count_steps(warm + measure)
        hist = np.asarray(jax.device_get(st["lat_hist"] - base))
        return {"hist": hist, **percentiles(hist, LATENCY_QS)}

    def run_latency_batch(self, traffic: Traffic, seeds,
                          warm: int = 200, measure: int = 600) -> dict:
        """Batched ``run_latency``: per-replica histograms and percentile
        lists (``{"p0.5": [R floats], ...}``; NaN where a replica ejected
        nothing in the window)."""
        with tracing.span("runner.prepare"):
            st = self.make_batch_state(traffic, seeds)
        st = self.run_chunk_batch(st, traffic, warm)
        base = st["lat_hist"] + 0
        st = self.run_chunk_batch(st, traffic, measure)
        self._count_steps((warm + measure) * len(seeds))
        hist = np.asarray(jax.device_get(st["lat_hist"] - base))  # [R, bins]
        per = [percentiles(row, LATENCY_QS) for row in hist]
        out = {"hist": hist}
        for q in LATENCY_QS:
            k = f"p{q}"
            out[k] = np.asarray([p[k] for p in per])
        return out

    # ------------------------------------------------------------------ #
    # open-loop serving drivers (Traffic("arrival"))
    # ------------------------------------------------------------------ #
    def _serving_snapshot(self, st) -> dict:
        # fresh buffers (`+ 0`): the state is about to be donated
        return {k: st[k] + 0 for k in ("lat_hist", "ejected", "arrived",
                                       "arr_drop", "pool_stall")}

    @staticmethod
    def _serving_metrics(m: dict, S: int, measure: int) -> dict:
        """Window deltas -> serving record (offered/delivered in
        packets/slot/endpoint, latency percentiles incl. the SLO tail)."""
        hist = np.asarray(m["lat_hist"])
        delivered = np.asarray(m["ejected"], np.int64)
        accepted = np.asarray(m["arrived"], np.int64)
        dropped = np.asarray(m["arr_drop"], np.int64)
        denom = float(S * measure)
        out = {
            "hist": hist,
            "offered": (accepted + dropped) / denom,
            "delivered": delivered / denom,
            "dropped": dropped,
            "pool_stall": np.asarray(m["pool_stall"], np.int64),
        }
        if hist.ndim == 1:
            out.update(percentiles(hist, LATENCY_QS))
            out["offered"] = float(out["offered"])
            out["delivered"] = float(out["delivered"])
            out["dropped"] = int(out["dropped"])
            out["pool_stall"] = int(out["pool_stall"])
        else:
            per = [percentiles(row, LATENCY_QS) for row in hist]
            for q in LATENCY_QS:
                k = f"p{q}"
                out[k] = np.asarray([p[k] for p in per])
        return out

    def run_serving(self, traffic: Traffic, warm: int = 200,
                    measure: int = 600, seed: int = 0) -> dict:
        """Open-loop load-latency measurement: warm the arrival source,
        then measure offered vs delivered rate, source drops, and the
        latency histogram (birth-slot based, so source queueing counts)
        over ``measure`` slots.  One device fetch, like the other
        drivers."""
        if traffic.pattern != "arrival":
            raise ValueError(f"run_serving needs Traffic('arrival'), got "
                             f"{traffic.pattern!r}")
        with tracing.span("runner.prepare"):
            st = self.make_state(traffic, seed)
        st = self.run_chunk(st, traffic, warm)
        base = self._serving_snapshot(st)
        st = self.run_chunk(st, traffic, measure)
        self._count_steps(warm + measure)
        m = jax.device_get({k: st[k] - base[k] for k in base})
        return {**self._serving_metrics(m, self.S, measure), "state": st}

    def run_serving_batch(self, traffic: Traffic, seeds, warm: int = 200,
                          measure: int = 600) -> dict:
        """Batched ``run_serving``: per-replica ``[R]`` arrays (percentile
        entries NaN where a replica delivered nothing in the window)."""
        if traffic.pattern != "arrival":
            raise ValueError(f"run_serving needs Traffic('arrival'), got "
                             f"{traffic.pattern!r}")
        with tracing.span("runner.prepare"):
            st = self.make_batch_state(traffic, seeds)
        st = self.run_chunk_batch(st, traffic, warm)
        base = self._serving_snapshot(st)
        st = self.run_chunk_batch(st, traffic, measure)
        self._count_steps((warm + measure) * len(seeds))
        m = jax.device_get({k: st[k] - base[k] for k in base})
        return {**self._serving_metrics(m, self.S, measure), "state": st}

    # ------------------------------------------------------------------ #
    # fault injection: live table updates + resilience driver
    # ------------------------------------------------------------------ #
    def update_tables(self, st, delta):
        """Scatter a :class:`repro.core.routing.TableDelta` into the
        state-resident device tables **in place** (donation-safe: the old
        table buffers are consumed).  Works on scalar and batched states;
        ``st`` is consumed — keep the returned dict.
        """
        if not self.has_failures:
            raise RuntimeError(
                "update_tables needs a Simulator built with a failure "
                "schedule (failures=...)")
        st = dict(st)
        batched = st["ejected"].ndim == 1
        n, w = self.N, self.W
        link_up = jnp.asarray(delta.link_up.reshape(-1))
        switch_up = jnp.asarray(delta.switch_up)
        if batched:
            r = st["ejected"].shape[0]
            link_up = jnp.tile(link_up[None], (r, 1))
            switch_up = jnp.tile(switch_up[None], (r, 1))
        st["link_up"], st["switch_up"] = link_up, switch_up
        k = delta.n_affected
        if k:
            rows = jnp.asarray(
                (delta.leaf_rows.astype(np.int64)[:, None] * n
                 + np.arange(n)[None, :]).reshape(-1).astype(np.int32))
            scatter = _scatter_rows_batch if batched else _scatter_rows
            with _quiet_cpu_donation():
                if self.fused:
                    packed = pack_route_rows(delta.min_rows, delta.away_rows,
                                             delta.dist_rows, self.P)
                    st["tbl_rows"] = scatter(
                        st["tbl_rows"], rows,
                        jnp.asarray(packed.reshape(k * n, self.K)))
                else:
                    st["tbl_min"] = scatter(
                        st["tbl_min"], rows,
                        jnp.asarray(delta.min_rows.reshape(k * n, w)))
                    st["tbl_dist"] = scatter(
                        st["tbl_dist"], rows,
                        jnp.asarray(delta.dist_rows.reshape(-1)))
        return st

    def drop_dead_packets(self, st):
        """Free every packet stranded on a dead element (the
        ``policy="drop"`` schedule option): whole input+output queues of
        dead switches and whole output queues feeding dead links — every
        packet there is unreachable until restore, so the drop is exact.
        Freed ids return to the free-list ring; ``fail_drop`` counts them.
        Host-side surgery on a **scalar** state (called at failure slots,
        never in the hot path)."""
        if st["ejected"].ndim != 0:
            raise ValueError("drop_dead_packets works on scalar states")
        N, P, V = self.N, self.P, self.V
        link_up = np.asarray(st["link_up"]).reshape(N, P)
        switch_up = np.asarray(st["switch_up"])
        # output queues die with their link (covers dead switches — all
        # their links are down); input queues die only with their switch
        # (packets already received at a live switch can still route out)
        dead_out_q = np.repeat(~link_up.reshape(-1), V)            # [NQ]
        dead_in_q = np.repeat(~switch_up, P * V)                   # [NQ]
        freed = []

        def clear(buf, head, ln, depth, dead):
            rows = np.nonzero(dead & (ln > 0))[0]
            for qi in rows:
                idx = (head[qi] + np.arange(ln[qi])) % depth
                freed.extend(int(x) for x in buf[qi, idx])
                ln[qi] = 0
            return ln

        qlen = np.array(st["qlen"])
        oq_len = np.array(st["oq_len"])
        qlen = clear(np.asarray(st["qbuf"]), np.asarray(st["qhead"]),
                     qlen, self.Q, dead_in_q)
        oq_len = clear(np.asarray(st["oq_buf"]), np.asarray(st["oq_head"]),
                       oq_len, self.cfg.out_queue, dead_out_q)
        st = dict(st)
        if freed:
            fl_buf = np.array(st["fl_buf"])
            head, ln = int(st["fl_head"]), int(st["fl_len"])
            pos = (head + ln + np.arange(len(freed))) % self.pool
            fl_buf[pos] = freed
            st["fl_buf"] = jnp.asarray(fl_buf)
            st["fl_len"] = jnp.asarray(ln + len(freed), jnp.int32)
            st["fail_drop"] = st["fail_drop"] + jnp.int32(len(freed))
        st["qlen"] = jnp.asarray(qlen)
        st["oq_len"] = jnp.asarray(oq_len)
        return st

    def run_resilience(self, traffic: Traffic, warm: int = 200,
                       measure: int = 400, seed: int = 0,
                       chunk: int = 32) -> dict:
        """Throughput + latency under the attached failure schedule.

        Advances in ``chunk``-slot jitted runs plus single-slot remainder
        steps (compile set = {chunk, 1}, independent of where events
        land), applying each schedule transition at its slot boundary via
        :meth:`RoutingTables.apply_failures` → :meth:`update_tables`
        (+ :meth:`drop_dead_packets` under the ``"drop"`` policy).
        Transitions at the warm boundary apply before the snapshot.  On
        return the host tables are restored to pristine, so cached
        simulators stay reusable (BFS is deterministic — restoration is
        exact).
        """
        if not self.has_failures:
            raise ValueError(
                "run_resilience needs a Simulator built with a non-empty "
                "FailureSchedule (failures=...); use run_throughput for "
                "pristine fabrics")
        sched = self.failures
        drop = sched.policy == "drop"
        trans = sched.transitions()
        with tracing.span("runner.prepare"):
            st = self.make_state(traffic, seed)
        now = 0
        ti = 0
        active: list = []

        def advance_to(st, target):
            nonlocal now
            while now + chunk <= target:
                st = self.run_chunk(st, traffic, chunk)
                now += chunk
            while now < target:
                st = self.run_chunk(st, traffic, 1)
                now += 1
            return st

        def apply_due(st, boundary):
            nonlocal ti
            while ti < len(trans) and trans[ti][0] <= boundary:
                slot, downs, ups = trans[ti]
                st = advance_to(st, slot)
                delta = self.tables.apply_failures(down=downs, up=ups)
                st = self.update_tables(st, delta)
                active.extend(downs)
                for ev in ups:
                    if ev in active:
                        active.remove(ev)
                if drop and downs:
                    st = self.drop_dead_packets(st)
                ti += 1
            return st

        try:
            st = apply_due(st, warm)
            st = advance_to(st, warm)
            base = {k: st[k] + 0 for k in ("ejected", "hop_sum",
                                           "pool_stall", "fail_drop",
                                           "lat_hist")}
            st = apply_due(st, warm + measure)
            st = advance_to(st, warm + measure)
            self._count_steps(warm + measure)
            m = jax.device_get({k: st[k] - base[k] for k in base}
                               | {"ejected_total": st["ejected"]})
        finally:
            if active or ti:
                # exact pristine restore (BFS is deterministic), so the
                # shared host tables are clean for the next caller
                self.tables.apply_failures(up=tuple(active))
        hist = np.asarray(m["lat_hist"])
        return {
            "throughput": int(m["ejected"]) / (self.S * measure),
            "avg_hops": int(m["hop_sum"]) / max(int(m["ejected"]), 1),
            "ejected": int(m["ejected_total"]),
            "pool_stall": int(m["pool_stall"]),
            "fail_drop": int(m["fail_drop"]),
            "hist": hist,
            **percentiles(hist, LATENCY_QS),
            "state": st,
        }

    def run_completion(self, traffic: Traffic, expected: int,
                       chunk: int = 128, max_slots: int = 100_000,
                       seed: int = 0, state: Optional[dict] = None,
                       budget_chunks: Optional[int] = None,
                       done=None) -> dict:
        """Run until all ``expected`` packets are delivered (collectives).

        The chunk loop runs entirely on device (``lax.while_loop``); the
        reported ``slots`` is the exact slot the ejection counter crossed
        ``expected``, not the enclosing chunk boundary.  Accepts scalar or
        batched (``make_batch_state``) state; with a replica axis, ``slots``
        / ``completed`` / ``pool_stall`` come back as per-replica arrays and
        the loop stops once *all* replicas have completed.

        A caller-provided ``state`` is consumed (its buffers are donated to
        the device loop) — reuse the returned ``state`` instead.

        ``budget_chunks=B`` bounds one call to at most ``B`` chunk bodies —
        the checkpointable segment used by
        :mod:`repro.runtime.resilient`.  The result then carries
        ``running`` (True while delivery is still in progress) and
        ``done`` (the per-replica completion-slot array to thread into the
        next segment alongside ``state``); a chain of bounded segments is
        bitwise-identical to one unbounded call.
        """
        if state is None:
            with tracing.span("runner.prepare"):
                state = self.make_state(traffic, seed)
        st = state
        # p_bh packs the born slot above the hop byte; past 2^23 slots the
        # shifted value would wrap int32 and corrupt latency measurement
        assert max_slots < (1 << 23), \
            "max_slots overflows the p_bh born-slot packing (< 2^23)"
        st = {k: jnp.asarray(v) for k, v in st.items()}
        with _quiet_cpu_donation():
            if budget_chunks is None:
                st, done = self._completion_loop(st, self._tables(), traffic,
                                                 expected, chunk, max_slots)
            else:
                done = (jnp.full_like(st["ejected"], -1) if done is None
                        else jnp.asarray(done, jnp.int32))
                st, done = self._completion_loop_bounded(
                    st, done, self._tables(), traffic, expected, chunk,
                    max_slots, int(budget_chunks))
        done = np.asarray(done)
        final = np.asarray(st["slot"])
        slots = np.where(done >= 0, done, final)
        completed = done >= 0
        out = {"state": st}
        if budget_chunks is not None:
            out["done"] = done
            out["running"] = bool((~(done >= 0)).any()
                                  and final.max() < max_slots)
        if done.ndim == 0:
            return {"slots": int(slots), "completed": bool(completed),
                    "pool_stall": int(st["pool_stall"]), **out}
        return {"slots": slots, "completed": completed,
                "pool_stall": np.asarray(st["pool_stall"]), **out}

    def run_completion_batch(self, traffic: Traffic, expected: int, seeds,
                             chunk: int = 128,
                             max_slots: int = 100_000) -> dict:
        """Batched ``run_completion`` over fresh per-seed replica states."""
        with tracing.span("runner.prepare"):
            state = self.make_batch_state(traffic, seeds)
        return self.run_completion(
            traffic, expected, chunk=chunk, max_slots=max_slots,
            state=state)

    # ------------------------------------------------------------------ #
    # compiled workload programs (repro.workloads)
    # ------------------------------------------------------------------ #
    @staticmethod
    def program_traffic(program) -> Traffic:
        """The static :class:`Traffic` shape of a
        :class:`repro.workloads.CompiledProgram` — only phase count and
        schedule; the arrays ride in the state, so same-shaped programs
        share one compiled executable."""
        return Traffic("program", n_phases=program.n_phases,
                       schedule=program.schedule, window=program.window)

    def make_program_state(self, program, seed: int = 0) -> dict:
        """State for a compiled program run: the base simulator state plus
        the device-resident schedule arrays and the scheduler registers
        (``phase`` counter, per-phase ``phase_done`` completion slots,
        ``phase_ok`` flags, and the phase-reset key ``key0``)."""
        if program.n_endpoints != self.S:
            raise ValueError(
                f"program compiled for {program.n_endpoints} endpoints, "
                f"fabric has {self.S}")
        i32 = jnp.int32
        st = self.make_state(self.program_traffic(program), seed)
        # copies, not aliases: the state pytree is donated to the program
        # loop, and donating the CompiledProgram's own arrays would consume
        # them after one run
        st["prog_partner"] = jnp.array(program.partner, i32)
        st["prog_packets"] = jnp.array(program.packets, i32)
        st["prog_expected"] = jnp.array(program.expected, i32)
        st["prog_expected_cum"] = jnp.array(program.expected_cum, i32)
        st["phase"] = jnp.zeros((), i32)
        st["phase_done"] = jnp.full((program.n_phases,), -1, i32)
        st["phase_ok"] = jnp.zeros((program.n_phases,), bool)
        # fresh buffer (`+ 0`), not an alias: the whole state pytree is
        # donated to the program loop, and a donated buffer may only
        # appear once
        st["key0"] = st["key"] + 0
        return st

    # compiled-schedule arrays that are replica-invariant: one device copy
    # shared across the vmap axis (key -> unbatched ndim, used to detect
    # whether a caller-supplied state left them unstacked)
    _PROG_SHARED = {"prog_partner": 2, "prog_packets": 2,
                    "prog_expected": 1, "prog_expected_cum": 1}

    def make_program_batch_state(self, program, seeds) -> dict:
        """``make_program_state`` stacked on a leading replica axis.

        The compiled schedule arrays (``prog_partner`` etc.) are identical
        for every replica, so they are kept as **one** shared copy instead
        of being stacked ``R``-fold — on a rounds-heavy program at paper
        scale the ``[n_phases, S]`` tables are the largest state entries,
        and the program loop vmaps them with ``in_axes=None``.
        """
        states = [self.make_program_state(program, seed=int(s))
                  for s in seeds]
        shared = {k: states[0][k] for k in self._PROG_SHARED}
        for st in states:
            for k in self._PROG_SHARED:
                del st[k]
        batch = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)
        batch.update(shared)
        return batch

    @functools.partial(jax.jit, static_argnums=(0, 3, 4, 5, 6),
                       donate_argnums=(1,))
    def _program_loop(self, st, tb, traffic: Traffic, chunk: int,
                      max_slots: int, budget: Optional[int] = None):
        """Device-side program executor: one ``lax.while_loop`` drives all
        phases of all replicas — the phase counter, per-phase ejection
        targets, and exact completion slots all live on device, so an
        R-replica, P-phase collective is one device computation with zero
        per-phase host round-trips.

        Each chunk body steps up to ``chunk`` slots and ends at the slot
        where the program completes (every replica, when batched), so the
        final state's ``slot`` is the completion slot, not the next chunk
        boundary.  ``max_slots`` is tested between chunk bodies only: a run
        that times out stops on a chunk boundary.

        ``budget=B`` runs at most ``B`` chunk bodies, then returns control
        to the host — the checkpointable chunk boundary for resumable
        collective runs.  The budget only counts chunk bodies, so a chain
        of bounded segments, including segments re-entered from a restored
        snapshot, replays the unbounded loop bitwise.
        """
        batched = st["ejected"].ndim == 1
        sim = self._bound(tb)
        step = lambda s: sim._step(s, traffic, chunk=chunk,
                                   max_slots=max_slots)
        if batched:
            # replica-invariant schedule arrays ride unbatched
            # (in_axes/out_axes None): one shared device copy, no R-fold
            # gather traffic.  A caller-built state that did stack them is
            # detected by ndim and mapped normally.
            axes = {k: None if st[k].ndim == self._PROG_SHARED.get(k, -1)
                    else 0 for k in st}
            step = jax.vmap(step, in_axes=(axes,), out_axes=axes)

        if traffic.schedule == "window":
            def running(s):
                return ~jnp.all(s["phase_done"][..., -1] >= 0)
        else:
            def running(s):
                return ~jnp.all(s["phase"] >= traffic.n_phases)

        def chunk_body(carry):
            s, it = carry
            s, _ = jax.lax.while_loop(
                lambda c: (c[1] < chunk) & running(c[0]),
                lambda c: (step(c[0]), c[1] + 1),
                (s, jnp.zeros((), jnp.int32)))
            return s, it + 1

        def cond(carry):
            s, it = carry
            go = running(s)
            if traffic.schedule == "window":
                go &= jnp.max(s["slot"]) < max_slots
            # a stuck barrier phase force-advances at its chunk-granular
            # max_slots budget, so the phase counter always reaches
            # n_phases without a slot test here
            if budget is not None:
                go &= it < budget
            return go

        st, _ = jax.lax.while_loop(cond, chunk_body,
                                   (st, jnp.zeros((), jnp.int32)))
        return st

    def _program_running(self, st, traffic: Traffic,
                         max_slots: int) -> bool:
        """Host-side mirror of the program loop's continue condition."""
        if traffic.schedule == "window":
            live = bool((np.asarray(st["phase_done"])[..., -1] < 0).any())
            return live and int(np.asarray(st["slot"]).max()) < max_slots
        return bool((np.asarray(st["phase"]) < traffic.n_phases).any())

    def run_program(self, program, *, chunk: int = 16,
                    max_slots: int = 60_000, seed: int = 0, seeds=None,
                    state: Optional[dict] = None,
                    budget_chunks: Optional[int] = None) -> dict:
        """Run a compiled :class:`repro.workloads.CompiledProgram` to
        completion, entirely on device.

        One of ``seed`` (scalar run), ``seeds`` (fresh batched run), or
        ``state`` (pre-built scalar/batched state — consumed, like
        ``run_completion``).  Returns ``slots`` (total), ``completed``,
        ``pool_stall``, and ``phase_slots`` (``[..., n_phases]`` — exact
        per-phase durations under ``barrier``, cumulative completion slots
        under ``window``); per-replica arrays when batched.

        The device loop stops at the slot where the program completes (the
        slowest replica's, when batched); it steps nothing past it.
        ``chunk`` sets only how often ``max_slots`` is tested (a run that
        times out stops on a chunk boundary) and the unit of a segment:
        ``budget_chunks=B`` bounds one call to at most
        ``B`` chunk bodies (the checkpointable segment used by
        :mod:`repro.runtime.resilient`); the result then carries
        ``running`` — True while the program has phases left — and the
        other fields are partial until it flips False.  A chain of bounded
        segments over the same ``state`` is bitwise-identical to one
        unbounded call.
        """
        assert max_slots < (1 << 23), \
            "max_slots overflows the p_bh born-slot packing (< 2^23)"
        traffic = self.program_traffic(program)
        fresh = state is None
        if fresh:
            with tracing.span("runner.prepare"):
                st = (self.make_program_batch_state(program, seeds)
                      if seeds is not None
                      else self.make_program_state(program, seed))
        else:
            st = state
        st = {k: jnp.asarray(v) for k, v in st.items()}
        with _quiet_cpu_donation():
            st = self._program_loop(
                st, self._tables(), traffic, chunk, max_slots,
                None if budget_chunks is None else int(budget_chunks))
        done = np.asarray(st["phase_done"])
        ok = np.asarray(st["phase_ok"])
        if traffic.schedule == "window":
            # phases the run never completed report the final slot
            final = np.asarray(st["slot"])[..., None]
            done = np.where(done >= 0, done, final)
            slots = done[..., -1]
            if fresh and budget_chunks is None:
                # the loop stops at the slowest replica's completion slot
                # (or a timed-out chunk boundary): the final slot is what
                # was stepped, per replica
                self._count_steps(int(final.sum()))
        else:
            slots = done.sum(axis=-1)
        completed = ok.all(axis=-1)
        out = {"phase_slots": done, "state": st}
        if budget_chunks is not None:
            out["running"] = self._program_running(st, traffic, max_slots)
        if completed.ndim == 0:
            return {"slots": int(slots), "completed": bool(completed),
                    "pool_stall": int(st["pool_stall"]), **out}
        return {"slots": slots, "completed": completed,
                "pool_stall": np.asarray(st["pool_stall"]), **out}


def percentiles(hist: np.ndarray, qs) -> dict:
    """Latency percentiles from a histogram whose bin index *is* the latency
    in slots (packets are recorded at ``clip(slot - born + 1, ...)``).

    Uniformly ``float`` valued: completed bins return ``float(bin)`` and
    empty histograms ``float("nan")`` — downstream aggregation never sees a
    mixed int/float stream.
    """
    total = hist.sum()
    out = {}
    if total == 0:
        return {f"p{q}": float("nan") for q in qs}
    cum = np.cumsum(hist)
    for q in qs:
        out[f"p{q}"] = float(np.searchsorted(cum, q * total))
    return out
