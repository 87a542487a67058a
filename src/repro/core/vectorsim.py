"""Batched round-level simulation backend: whole sweep grids in one
compiled call.

The discrete-event engines (``cluster.Cluster``) pay one Python event loop
per grid cell; a sweep (clients x seeds x configs) only scales with cores.
This module decouples scenario coverage from per-event Python dispatch the
same way *Compartmentalization* decouples the protocol from its bottleneck:
the per-request message flow of Paxos / PigPaxos / EPaxos is re-expressed
as pure array math — a ``lax.scan`` over requests, ``vmap`` over the grid —
so an entire scenario grid is ONE jitted XLA call.

Model (request level, mirroring the flattened ``engine="fast"`` semantics):

* **closed-loop client credit** — each client holds one outstanding request;
  the scan pops the earliest-ready client, walks its request through the
  protocol's hop/CPU pipeline, and credits the client back at reply time;
* **per-node CPU-queue accumulators** — every node is a FIFO server
  (service = CostModel cpu cost per message, §2.2); queueing is modeled by
  reserving CPU in request order (``max(arrival, cpu_free) + cost``), with
  exact FIFO ordering *within* a request's reply fan-in (sort + cumulative
  max over the group grid);
* **rotating relay choice** sampled per group per round (§3.1), static
  relays and explicit (e.g. per-region WAN) groups supported;
* **link latencies** drawn per hop from the ``Topology`` spec: LAN base +
  Exp(jitter), or the WAN one-way region matrix (§5.3);
* **PRC thresholds** q_i = n_i - PRC with the §4.1 liveness adjustment, and
  the §4.3 single-group global-majority shortcut.

Classic Paxos is the degenerate group structure (N-1 singleton groups with
direct-message costs); EPaxos gets its own symmetric kernel: random
per-request command leader, PreAccept broadcast, fast-quorum commit — and a
**conflict/slow-path model**: each request draws its key from the
workload's distribution (uniform / zipfian via the cached CDF / hot-key
conflict), requests whose PreAccept round races the previous same-key
instance's propagation window take the Paxos-accept slow path (a second
fan-out/fan-in round), and execution waits for the predecessor's commit to
be known (dependency-order gate).  Throughput tracks the fast DES within
~10% up to c=0.5 (tests/test_epaxos_recovery.py).

**Leased leader reads** (group kernel only): a workload with
``read_ratio`` > 0 and ``read_path="lease"`` models the leader serving
reads locally under a held lease — each scan-step burst draws a per-request
read mask (an extra fold of the step key; the write path's draw order is
untouched), the leader FIFO becomes a varying-service Lindley chain
(writes cost the full round's leader work, leased reads cost only
request-ingest + reply), and read requests skip the entire follower
fan-out: no relay hops, no follower CPU work, no aggregate fan-in, and no
commit (``committed`` counts writes only — reads never touch the log).
``read_path="log"`` needs no kernel support at all: log reads flow through
phase 2 exactly like writes, so only the expected wire sizes change (gets
carry no payload out, puts carry none back).  The lease itself is assumed
HELD for the whole run — grant/renewal traffic, expiry windows, and clock
drift are DES-only (that is where lease safety is audited); the batch
model is the steady-state throughput/latency envelope of an uncontested
lease.  Per-node message loads keep their write-path meaning (messages
per committed write; read traffic at the leader is not counted).

**Fault masks** (``repro.faults.FaultPlan.to_masks``): deterministic
crash/recover windows and whole-run gray/slow nodes are expressible as
time-varying per-node availability masks — a hop arriving at a down node is
*deferred* to the window's end (the node drains its backlog at recovery),
relays are sampled among the currently-up group members (matching the DES
leader's gray-listing behavior after one timeout), and slow nodes add a
constant one-way latency to every touching hop.  Group kernel only; mask
runs also emit a completion timeline (50 ms buckets, same format as the DES
``collect=("timeline",)`` extra) for throughput-dip/unavailability metrics.

Deliberately **not** modeled: partitions, drops, relay timeouts, late-vote
supplements, open-loop arrivals, (Pig)Paxos key sampling (keys never route
there), EPaxos fault masks (instance recovery is a DES-only protocol
phase), EPaxos dependency-graph wall-time (Tarjan costs no virtual
time), quorum/follower reads (the probe / rinse / re-probe state machine
has no array form — quorum-read scenarios are DES-authoritative), lease
grant/expiry dynamics and clock drift (see the leased-reads paragraph
above), and reads combined with fault masks, leader batching, or the
EPaxos kernel (``build_config`` rejects those loudly) — scenarios that
need those stay on the DES (`Scenario.batch_ok` marks the eligible
ones).  A crashed follower's
vote is deferred, not lost, so plans must leave every group's PRC threshold
reachable without the down members (single crashes with ``prc >= 1``, or
Paxos's singleton groups) — the DES relay-timeout fallback has no batch
equivalent.

Outputs match the DES ``Stats`` summary (committed throughput, latency
percentiles measured at the client over the [warmup, warmup+duration]
window, per-node message loads M_l / M_f) within a few percent of
``Cluster(engine="fast")`` — see tests/test_vectorsim.py.
"""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, PartitionSpec

from .messages import HEADER_BYTES, CostModel
from .pig import partition_followers, required_per_group
from .quorums import fast_quorum, majority
from .segscan import seg_cummax, seg_cumsum

# measurement harness constants — keep identical to cluster.Cluster
_DRAIN_S = 0.2          # post-stop drain window (Cluster.measure)
_CLIENT_START = 20e-3   # Cluster.add_clients start_at
_CLIENT_STAGGER = 1e-4  # per-client start stagger
_TL_BUCKET = 0.05       # timeline bucket (= runner.TIMELINE_BUCKET_S)

_MAX_STEPS = 400_000    # hard cap for the exhausted-retry loop

# static-shape signature -> number of XLA traces (tests assert a whole grid
# compiles exactly once; see trace_counts())
_TRACE_COUNTS: Dict[tuple, int] = {}


def trace_counts() -> Dict[tuple, int]:
    return dict(_TRACE_COUNTS)


# the group kernel's slot<->group lookups are one-hot selects up to one lane
# tile of follower slots (see _group_cell), batched gathers above it
_ONEHOT_MAX_F = 128
# group-kernel programs traced per lookup form ("onehot" | "gather")
_INDEX_COUNTS: Dict[str, int] = {}


def index_counts() -> Dict[str, int]:
    return dict(_INDEX_COUNTS)


# ===================================================================== config
@dataclasses.dataclass
class SimConfig:
    """One protocol deployment, lowered to arrays (leader = node 0).

    ``kind`` selects the kernel: "group" covers Paxos (singleton groups,
    direct-message costs) and PigPaxos (relay groups); "epaxos" is the
    symmetric random-leader kernel.
    """
    kind: str
    n: int
    members: np.ndarray        # (r, g) follower node ids, -1 padding
    sizes: np.ndarray          # (r,) group sizes (0 = padded group)
    thresh: np.ndarray         # (r,) relay flush threshold incl. the relay
    static_relay: bool
    majority: int
    region_of: np.ndarray      # (n,) region per node (all 0 for LAN)
    region_latency: np.ndarray  # (nreg, nreg) one-way base seconds
    jitter: float
    costs: Dict[str, float]    # c_req/c_fanout/c_rel/c_repl/c_agg/c_replycl
    label: str = ""
    # fault masks (None = fault-free): down-windows (n, W, 2) [lo, hi) with
    # +inf padding, and per-node whole-run extra one-way latency (n,)
    down: Optional[np.ndarray] = None
    slow: Optional[np.ndarray] = None
    # EPaxos conflict model (epaxos kernel only): the workload's key
    # distribution — 0 uniform, 1 zipfian (key_cdf), 2 hot-key conflict
    key_mode: int = 0
    n_keys: int = 1000
    conflict_rate: float = 0.0
    key_cdf: Optional[np.ndarray] = None
    # leased-leader-read model (group kernel only): fraction of requests
    # served locally at the leader under a held lease (0 = write path only)
    read_ratio: float = 0.0

    @property
    def rmax(self) -> int:
        return self.members.shape[0]

    @property
    def gmax(self) -> int:
        return self.members.shape[1]


def _expected_wires(workload) -> Dict[str, float]:
    """Expected wire sizes per message role (costs are linear in bytes, so
    using the expectation is exact for mean CPU load)."""
    wf = 0.5
    payload = 8.0
    if workload is not None:
        wf = float(workload.write_fraction)
        if getattr(workload, "read_ratio", None) is not None:
            wf = 1.0 - float(workload.read_ratio)
        if workload.payload_choices:
            w = np.asarray(workload.payload_weights
                           or [1.0] * len(workload.payload_choices), float)
            sizes = np.asarray([float(s) for s in workload.payload_choices])
            payload = float((sizes * w / w.sum()).sum())
        else:
            payload = float(workload.payload_bytes)
    cmd = 16.0 + wf * payload                      # Command.wire_size
    return {
        "req": HEADER_BYTES + cmd,                 # ClientRequest
        "p2a": HEADER_BYTES + 16 + cmd,            # P2a
        "p2b": float(HEADER_BYTES),                # P2b
        # gets return the stored value (= a put payload); puts return None
        "reply_cl": HEADER_BYTES + 8 + (1.0 - wf) * payload,
        "cmd": cmd,
    }


def build_config(protocol: str, n: int, pig=None, topo=None, workload=None,
                 cost: Optional[CostModel] = None, label: str = "",
                 masks: Optional[Dict[str, np.ndarray]] = None,
                 batch_m: int = 1) -> SimConfig:
    """Lower a (protocol, n, PigConfig, Topology, WorkloadConfig) deployment
    to the array form the batched kernels consume.  ``masks`` is the fault
    lowering produced by ``repro.faults.FaultPlan.to_masks`` — down-windows
    and slow vectors (group kernel only).

    ``batch_m`` models leader-side request batching (``BatchConfig`` with a
    full batch of m on every slot — the saturation regime): one "request"
    through the kernel is a whole batch, with per-batch cost = fixed +
    per-command marginal, exactly the DES cost model — m ClientRequest
    ingests, ONE phase-2 fan-out carrying the batched P2a (8-byte batch
    header + m commands), fixed-size votes/aggregates unchanged, m serial
    client replies.  Callers divide the client count by m (m clients share
    one slot) and scale throughput back up; ``simulate_scenario`` does both.
    """
    cm = cost or CostModel()
    base, pb = cm.base, cm.per_byte
    w = _expected_wires(workload)
    if workload is not None and getattr(workload, "arrival", "closed") != "closed":
        raise ValueError("batch backend models closed-loop clients only")
    if batch_m < 1:
        raise ValueError("batch_m must be >= 1")
    if batch_m > 1 and protocol == "epaxos":
        raise ValueError("batch-backend batching is group-kernel only; "
                         "batched EPaxos runs are DES-authoritative "
                         "(leaderless per-node buffers interact with the "
                         "conflict model)")
    # leased-read model eligibility (see the module docstring): only the
    # group kernel's single-leader FIFO has a lease to serve reads under
    rr = (getattr(workload, "read_ratio", None)
          if workload is not None else None)
    rpath = (getattr(workload, "read_path", "log")
             if workload is not None else "log")
    lease_rr = 0.0
    if rr is not None and float(rr) > 0.0:
        if rpath == "quorum":
            raise ValueError(
                "batch backend models log and leased leader reads only; "
                "quorum reads (probe / rinse / re-probe rounds) have no "
                "array form — quorum-read scenarios are DES-authoritative")
        if rpath == "lease":
            if protocol == "epaxos":
                raise ValueError(
                    "leased reads are group-kernel only: epaxos is "
                    "leaderless (no leader lease to serve reads under) — "
                    "epaxos read scenarios need the DES quorum-read path")
            if masks is not None:
                raise ValueError(
                    "leased reads with fault masks need the DES: the "
                    "batch lease model assumes the lease is held for the "
                    "whole run, which a down-window invalidates")
            if batch_m > 1:
                raise ValueError(
                    "leased reads with leader batching are "
                    "DES-authoritative (reads bypass the batch buffer, so "
                    "the full-batch cost reparameterization no longer "
                    "describes the leader's service distribution)")
            lease_rr = float(rr)
    # batched P2a wire: BatchCmd = 8-byte batch header + m commands
    w_p2a = (w["p2a"] if batch_m == 1
             else HEADER_BYTES + 16 + 8 + batch_m * w["cmd"])
    down = slow = None
    if masks is not None:
        if protocol == "epaxos":
            raise ValueError("fault masks are group-kernel only; "
                             "EPaxos fault scenarios need the DES")
        d = np.asarray(masks["down"], dtype=np.float64)
        s = np.asarray(masks["slow"], dtype=np.float64)
        if d.shape[0] != n or s.shape[0] != n:
            raise ValueError(f"mask shape mismatch: n={n}, "
                             f"down={d.shape}, slow={s.shape}")
        if np.isfinite(d[..., 0]).any():
            down = d
        if (s > 0).any():
            slow = s
    # topology -> region arrays (LAN = one region)
    if topo is not None and topo.region_of is not None:
        region_of = np.asarray(topo.region_of, dtype=np.int32)
        region_latency = np.asarray(topo.region_latency, dtype=np.float64)
        jitter = float(topo.jitter)
    else:
        region_of = np.zeros(n, dtype=np.int32)
        blat = float(topo.base_latency) if topo is not None else 0.25e-3
        jitter = float(topo.jitter) if topo is not None else 0.05e-3
        region_latency = np.asarray([[blat]], dtype=np.float64)

    if protocol == "epaxos":
        # conflict model inputs: the workload's key distribution decides the
        # per-request conflict draw (interfering in-flight instances route
        # conflicted requests through the Paxos-accept slow path)
        key_mode, n_keys, crate, cdf = 0, 1000, 0.0, None
        if workload is not None:
            n_keys = int(getattr(workload, "n_keys", 1000))
            kd = getattr(workload, "key_dist", "uniform")
            if kd == "zipfian":
                from .cluster import zipf_cdf
                key_mode = 1
                cdf = zipf_cdf(n_keys, float(workload.zipf_theta))
            elif kd == "conflict":
                key_mode = 2
                crate = float(workload.conflict_rate)
        costs = {
            "c_req": base + pb * w["req"],
            # PreAccept / PreAcceptReply / ECommit all carry the O(N)
            # dependency bookkeeping term (CostModel §5.3)
            "c_pa": base + pb * (HEADER_BYTES + w["cmd"] + 12 + 8 * n)
            + cm.epaxos_extra_per_node * n,
            "c_par": base + pb * (HEADER_BYTES + 12 + 8 * n)
            + cm.epaxos_extra_per_node * n,
            "c_com": base + pb * (HEADER_BYTES + w["cmd"] + 12 + 8 * n)
            + cm.epaxos_extra_per_node * n,
            "c_replycl": base + pb * w["reply_cl"],
            # slow path (conflicts): EAccept carries the same O(N) payload
            # as PreAccept; EAcceptReply is a fixed-size ack
            "c_acc": base + pb * (HEADER_BYTES + w["cmd"] + 12 + 8 * n)
            + cm.epaxos_extra_per_node * n,
            "c_accr": base + pb * (HEADER_BYTES + 16),
        }
        return SimConfig(
            kind="epaxos", n=n,
            members=np.zeros((1, 1), np.int32), sizes=np.zeros(1, np.int32),
            thresh=np.zeros(1, np.int32), static_relay=False,
            majority=majority(n), region_of=region_of,
            region_latency=region_latency, jitter=jitter, costs=costs,
            label=label or f"epaxos/N={n}",
            key_mode=key_mode, n_keys=n_keys, conflict_rate=crate,
            key_cdf=cdf)

    followers = [i for i in range(1, n)]
    if protocol == "paxos" or pig is None:
        groups = [[f] for f in followers]
        thresh = [1] * len(groups)
        costs = {
            "c_req": batch_m * (base + pb * w["req"]),
            "c_fanout": base + pb * w_p2a,         # P2a direct (batched)
            "c_rel": 0.0,
            "c_repl": 0.0,
            "c_agg": base + pb * w["p2b"],         # P2b direct
            "c_replycl": batch_m * (base + pb * w["reply_cl"]),
        }
        static = True
    elif protocol == "pigpaxos":
        if pig.groups is not None:
            groups = [[m for m in grp if m != 0] for grp in pig.groups]
            groups = [g for g in groups if g]
        else:
            groups = partition_followers(followers, pig.n_groups)
        req = required_per_group(groups, n, pig.prc,
                                 pig.single_group_majority)
        thresh = [min(q, len(g)) for q, g in zip(req, groups)]
        pig_wrap = HEADER_BYTES + 8 + w_p2a        # PigFanout/PigRelayed(P2a)
        costs = {
            "c_req": batch_m * (base + pb * w["req"]),
            "c_fanout": base + pb * pig_wrap,
            "c_rel": base + pb * pig_wrap,
            "c_repl": base + pb * (HEADER_BYTES + 8 + w["p2b"]),  # PigReply
            "c_agg": base + pb * (HEADER_BYTES + 16),             # PigAggregate
            "c_replycl": batch_m * (base + pb * w["reply_cl"]),
        }
        static = not pig.rotate_relays
    else:
        raise ValueError(f"unknown protocol {protocol!r}")

    rmax = len(groups)
    gmax = max(len(g) for g in groups)
    members = np.full((rmax, gmax), -1, dtype=np.int32)
    sizes = np.zeros(rmax, dtype=np.int32)
    tarr = np.zeros(rmax, dtype=np.int32)
    for gi, g in enumerate(groups):
        members[gi, :len(g)] = g
        sizes[gi] = len(g)
        tarr[gi] = thresh[gi]
    return SimConfig(
        kind="group", n=n, members=members, sizes=sizes, thresh=tarr,
        static_relay=static, majority=majority(n), region_of=region_of,
        region_latency=region_latency, jitter=jitter, costs=costs,
        label=label or f"{protocol}/N={n}/R={rmax}", down=down, slow=slow,
        read_ratio=lease_rr)


# ================================================================ rate bound
def _estimate_rate(cfg: SimConfig, k: int) -> float:
    """Optimistic committed-req/s bound (steers the scan-step budget; an
    exhausted grid retries with 2x steps, so this only needs to be sane)."""
    c = cfg.costs
    reg_lat = cfg.region_latency
    leader_reg = int(cfg.region_of[0])
    b_cl = float(reg_lat[0, leader_reg])
    if cfg.kind == "epaxos":
        n = cfg.n
        per_node = 2.0 * (n - 1) * (c["c_pa"] + c["c_par"] + c["c_com"]) / n
        cpu_bound = 1.0 / per_node
        rt = 4 * (b_cl + cfg.jitter) + (n - 1) * c["c_pa"] + 3 * c["c_pa"]
        return min(cpu_bound, k / rt)
    sizes = cfg.sizes[cfg.sizes > 0].astype(float)
    ng = len(sizes)
    leader_cpu = c["c_req"] + ng * (c["c_fanout"] + c["c_agg"]) + c["c_replycl"]
    fol_cpu = (ng * (c["c_fanout"] + c["c_agg"])
               + 2.0 * float((sizes - 1).sum()) * (c["c_rel"] + c["c_repl"]))
    fol_bound = (cfg.n - 1) / fol_cpu if fol_cpu > 0 else float("inf")
    # unloaded round trip: client hops + 2 leader-side + 2 intra-group hops
    mem = cfg.members[cfg.members >= 0]
    b_med = float(np.median(reg_lat[leader_reg, cfg.region_of[mem]]))
    b_in = float(np.median(np.median(reg_lat, axis=0)))
    rt = (2 * b_cl + 2 * b_med + 2 * b_in + 6 * cfg.jitter + leader_cpu
          + c["c_fanout"] + float(sizes.max()) * (c["c_rel"] + c["c_repl"]))
    rr = cfg.read_ratio
    if rr > 0.0:
        # leased reads skip the fan-out entirely: leader work shrinks to
        # ingest + reply, followers see only the write fraction, and the
        # read round trip is two client hops plus the leader service
        w_read = c["c_req"] + c["c_replycl"]
        leader_cpu = rr * w_read + (1.0 - rr) * leader_cpu
        fol_bound = (fol_bound / (1.0 - rr)
                     if rr < 1.0 else float("inf"))
        rt = rr * (2 * b_cl + 2 * cfg.jitter + w_read) + (1.0 - rr) * rt
    return min(1.0 / leader_cpu, fol_bound, k / rt)


# ============================================================== group kernel
def _pct(sorted_vals, m, q):
    """np.percentile(..., q) with linear interpolation over the first ``m``
    entries of an ascending array (invalid entries sorted to +inf)."""
    mf = jnp.maximum(m.astype(jnp.float32), 1.0)
    idx = q * (mf - 1.0)
    lo = jnp.clip(jnp.floor(idx).astype(jnp.int32), 0, sorted_vals.shape[0] - 1)
    hi = jnp.clip(lo + 1, 0, sorted_vals.shape[0] - 1)
    frac = idx - lo.astype(jnp.float32)
    lov = sorted_vals[lo]
    hiv = jnp.where(hi < m, sorted_vals[hi], lov)
    v = lov * (1.0 - frac) + hiv * frac
    return jnp.where(m > 0, v, jnp.nan)


def _summarize(lat, t_fin, commit_t, active, ready, loadF, loadL, cell,
               nb: int = 0):
    stop, warmup, duration = cell["stop"], cell["warmup"], cell["duration"]
    in_lat = active & (t_fin >= warmup) & (t_fin <= stop)
    in_commit = active & (commit_t >= warmup) & (commit_t <= stop + _DRAIN_S)
    count = in_lat.sum()
    committed = in_commit.sum()
    vals = jnp.sort(jnp.where(in_lat, lat, jnp.inf))
    nf = jnp.maximum(count.astype(jnp.float32), 1.0)
    followers = cell["n_followers"].astype(jnp.float32)
    comf = jnp.maximum(committed.astype(jnp.float32), 1.0)
    out = {
        "throughput": count.astype(jnp.float32) / duration,
        "count": count,
        "committed": committed,
        "mean_s": jnp.where(count > 0,
                            jnp.where(in_lat, lat, 0.0).sum() / nf, jnp.nan),
        "median_s": _pct(vals, count, 0.5),
        "p25_s": _pct(vals, count, 0.25),
        "p75_s": _pct(vals, count, 0.75),
        "p99_s": _pct(vals, count, 0.99),
        "m_leader": loadL / comf,
        "m_follower": loadF / (followers * comf),
        "exhausted": jnp.min(ready) < stop,
    }
    if nb:
        # completion timeline (DES collect=("timeline",) format): counts of
        # client-visible completions per fixed virtual-time bucket from t=0
        ok = active & jnp.isfinite(t_fin) & (t_fin <= stop + _DRAIN_S)
        tb = jnp.where(ok, jnp.floor(t_fin / _TL_BUCKET), 0.0)
        tb = jnp.clip(tb.astype(jnp.int32), 0, nb - 1)
        out["timeline"] = jnp.zeros(nb, jnp.int32).at[tb].add(
            ok.astype(jnp.int32))
    return out


def _take(x, oh, axis: int = 0):
    """``jnp.take(x, idx, axis)`` for the one-hot mask ``oh = idx[...,
    None] == arange(x.shape[axis])`` of in-range indices, as a select and
    a max in place of a gather: exact for every non-NaN value, ±0 and ±inf
    included (the one selected entry meets only the dtype's lowest value).
    Serves both directions of the group kernel's layout: group -> slot
    (``idx = grp``) and slot -> group (``idx = rel_idx``, ``gstart``)."""
    with jax.named_scope("relay_index"):
        axis %= x.ndim
        k = oh.ndim - 1                        # idx.ndim
        post = x.ndim - axis - 1
        xm = jnp.moveaxis(x, axis, -1)
        xm = xm.reshape(xm.shape[:axis] + (1,) * k + xm.shape[axis:])
        m = oh.reshape((1,) * axis + oh.shape[:-1] + (1,) * post
                       + oh.shape[-1:])
        lowest = (-jnp.inf if jnp.issubdtype(x.dtype, jnp.floating)
                  else jnp.iinfo(x.dtype).min)
        return jnp.where(m, xm, lowest).max(-1)


def _lut(tab, a, b):
    """``tab[a, b]`` for in-range integer arrays ``a``, ``b`` (broadcast
    together) over a small table of static shape: one exact select per
    entry in place of a 2-D gather."""
    with jax.named_scope("relay_index"):
        out = jnp.broadcast_to(tab[0, 0], jnp.broadcast_shapes(
            jnp.shape(a), jnp.shape(b)))
        for i in range(tab.shape[0]):
            for j in range(tab.shape[1]):
                if i or j:
                    out = jnp.where((a == i) & (b == j), tab[i, j], out)
        return out


def _group_cell(cell, steps: int, kmax: int, breq: int,
                faulty: bool = False, nb: int = 0, kernel: str = "lax",
                obs: bool = False, read: bool = False):
    """Simulate one grid cell of the Paxos/PigPaxos group kernel.

    ``faulty`` (static) enables the fault-mask path: hop arrivals at a
    down node are deferred past its [lo, hi) window, relays are sampled
    among the currently-up group members, and slow nodes add their extra
    one-way latency to every touching hop.  The fault-free trace is
    untouched when False — the mask arrays are never read.

    ``obs`` (static) additionally emits a per-step leader-backlog series
    (the queueing wait W_L each scan step's first popped request just
    observed at the leader FIFO, bucketed over virtual time like the
    completion timeline) — the batch backend's cheap counterpart of the
    DES timeline sampler.  Requires ``nb > 0``; off by default so the
    scan's carry/output signature (and every cached compilation) is
    unchanged for existing callers.

    ``kernel`` (static) selects the reply fan-in implementation: "lax" is
    the sort + segmented-cummax oracle below; "pallas" routes the same
    order statistics through ``kernels.ops.seg_fanin`` (rank-counting
    Pallas kernel — interpret mode on CPU, native on TPU).

    ``read`` (static) enables the leased-leader-read model: each burst
    draws a per-request read mask (an EXTRA fold of the step key, so the
    write path's draw order is bit-identical to read=False), the leader
    ingress Lindley chain runs with per-request service (full round work
    for writes, ingest+reply for leased reads — exclusive prefix sums
    replace the constant-work ``kk_b * T_l`` terms), and read lanes skip
    the follower pipeline: no backlog contribution, no message loads, no
    commit (``commit_done = inf``), and the client reply returns straight
    from the leader.  When False the original constant-service expression
    is kept verbatim so existing compilations are unchanged.

    Three throughput tricks keep the scan XLA-friendly:

    * followers live on a FLAT axis (slots packed group-contiguously;
      ``grp``/``pos``/``gstart`` index the segments), so a heterogeneous
      config batch costs O(N-1) per step instead of O(rmax x gmax) padding;
      per-group order statistics are one lexicographic ``lax.sort`` (blocks
      stay in place) plus a segmented cumulative max;
    * each scan step pops the ``breq`` earliest-ready clients and pushes
      all of them through the pipeline at once — their leader ingress is
      serialized exactly (Lindley chain with constant per-request work),
      follower backlog reads within the burst share the pre-step snapshot
      (the same approximation the fluid model already makes across rounds);
    * the relay stages' slot<->group lookups (group -> slot broadcasts,
      the relay's slot, the segment starts, the WAN region table) are
      exact one-hot selects (``_take``, ``_lut``) over the cell's layout,
      its fixed masks built once before the scan: ``grp`` and ``gstart``
      are per-cell data, so under ``vmap`` a gather is a batched dynamic
      gather, which the TPU runs one element at a time.  The masks hold
      F x G entries per burst row, so up to ``_ONEHOT_MAX_F`` slots (one
      lane tile, the fan-in's unrolled width); wider grids keep the
      gathers.  Both forms give the same bits.
    """
    f32 = jnp.float32
    grp = cell["grp"]                         # (F,) group of each slot
    pos = cell["pos"]                         # (F,) position within group
    gstart = cell["gstart"]                   # (G,) segment start offsets
    sizes = cell["sizes"]                     # (G,)
    thresh = cell["thresh"]
    regF = cell["regF"]                       # (F,) follower regions
    reg_lat = cell["reg_lat"]                 # (nreg, nreg)
    leader_reg = cell["leader_reg"]
    jitter = cell["jitter"]
    (c_req, c_fanout, c_rel, c_repl, c_agg, c_replycl) = [
        cell["costs"][i] for i in range(6)]
    majf = cell["majority"].astype(f32)
    ng = cell["n_groups"]                     # real group count (int)
    ngf = ng.astype(f32)
    stop, warmup = cell["stop"], cell["warmup"]
    key = cell["key"]
    G = sizes.shape[0]
    F = grp.shape[0]
    B = breq

    onehot = F <= _ONEHOT_MAX_F
    form = "onehot" if onehot else "gather"
    _INDEX_COUNTS[form] = _INDEX_COUNTS.get(form, 0) + 1

    def take(x, idx, axis=0, oh=None):
        """``x`` at the in-range indices ``idx`` along ``axis``; ``oh`` is
        idx's one-hot where it is fixed for the cell."""
        if not onehot:
            return jnp.take(x, idx, axis=axis, mode="clip")
        if oh is None:
            oh = idx[..., None] == jnp.arange(x.shape[axis])
        return _take(x, oh, axis)

    lut = _lut if onehot else (lambda tab, a, b: tab[a, b])

    szf = sizes.astype(f32)
    grp_mask = sizes > 0
    valid = jnp.arange(F) < cell["n_followers"]
    seg_first = jnp.broadcast_to(pos == 0, (B, F))
    grp_b = jnp.broadcast_to(grp, (B, F))
    # the cell's fixed slot<->group indices: each slot's group, each
    # group's first slot and its (thresh-2)-th reply's slot
    g0 = jnp.clip(gstart, 0, F - 1)
    t_idx = jnp.clip(gstart + thresh - 2, 0, F - 1)
    oh_grp = oh_g0 = oh_t = None
    if onehot:
        oh_grp = grp[:, None] == jnp.arange(G)        # (F, G)
        oh_g0 = g0[:, None] == jnp.arange(F)          # (G, F)
        oh_t = t_idx[:, None] == jnp.arange(F)        # (G, F)
    kg = jnp.maximum(thresh - 2, 0)                # each group's flush rank
    kg_slot = take(kg, grp, 0, oh_grp)
    kk_r = jnp.arange(G, dtype=f32)
    kk_b = jnp.arange(B, dtype=f32)
    posf = pos.astype(f32)
    b_cl = reg_lat[0, leader_reg]
    npeers = jnp.maximum(sizes - 1, 0)
    acks = jnp.where(grp_mask, thresh, 0).astype(f32)
    # total leader work per request (early serialize + deferred late part)
    T_l = c_req + ngf * (c_fanout + c_agg) + c_replycl
    w_peer = c_rel + c_repl
    relay_work = c_fanout + npeers.astype(f32) * w_peer + c_agg  # (G,)

    # fault-mask state (read only when ``faulty``; see module docstring)
    downL = cell["downL"]                     # (W, 2) leader down-windows
    downF = cell["downF"]                     # (F, W, 2) per-slot windows
    slowF = cell["slowF"]                     # (F,) extra one-way seconds
    slowL = cell["slowL"]                     # scalar, node 0

    def defer(t, win):
        """Defer ``t`` past any [lo, hi) down-window containing it;
        ``win`` has shape (..., W, 2) broadcastable against t[..., None]."""
        inw = (t[..., None] >= win[..., 0]) & (t[..., None] < win[..., 1])
        return jnp.maximum(t, jnp.where(inw, win[..., 1], -jnp.inf).max(-1))

    ready0 = jnp.where(jnp.arange(kmax) < cell["k_clients"],
                       _CLIENT_START + _CLIENT_STAGGER * jnp.arange(kmax),
                       jnp.inf).astype(f32)

    def step_fn(carry, i):
        ready, cpuF, cpuL, loadF, loadL, dt_ewma, t_prev = carry
        with jax.named_scope("ingress"):
            k1, k2 = jax.random.split(jax.random.fold_in(key, i))
            neg, cids = lax.top_k(-ready, B)
            t0 = -neg                              # (B,) ascending issue times
            active = t0 < stop
            any_active = active[0]                 # actives are a prefix

            e = jax.random.exponential(k1, (B, 2 + 2 * G + 2 * F)) * jitter
            e_cl = e[:, :2]
            e_Lr = e[:, 2:2 + G]
            e_rL = e[:, 2 + G:2 + 2 * G]
            e_rp = e[:, 2 + 2 * G:2 + 2 * G + F]
            e_pr = e[:, 2 + 2 * G + F:]
            u_rel = jax.random.uniform(k2, (B, G))

            # leader ingress: exact FIFO over the burst (Lindley recursion
            # with constant work T_l), seeded by the accumulator.  W_L — the
            # queueing wait each request just experienced — doubles as the
            # stationary estimate of the wait its own aggregates will see one
            # RTT later.
            aL = t0 + b_cl + e_cl[:, 0]
            if faulty:
                # a request arriving at a down leader waits out the window
                # (the DES client's timeout retries land right after recovery)
                aL = defer(aL + slowL, downL)
            if read:
                # leased reads serve at the leader only: service is ingest +
                # reply, writes keep the full round's work.  The exclusive
                # prefix sum Wc generalizes the constant-work kk_b * T_l chain
                # (it reduces to it when every service equals T_l).
                u_read = jax.random.uniform(jax.random.fold_in(k2, 1), (B,))
                is_read = u_read < cell["read_ratio"]
                w_serve = jnp.where(is_read, c_req + c_replycl, T_l)
                Wc = jnp.cumsum(w_serve) - w_serve
                start_b = jnp.maximum(lax.cummax(aL - Wc) + Wc, cpuL + Wc)
                cpuL_next = jnp.maximum(
                    cpuL, jnp.where(active, start_b + w_serve, -jnp.inf).max())
            else:
                start_b = jnp.maximum(lax.cummax(aL - kk_b * T_l) + kk_b * T_l,
                                      cpuL + kk_b * T_l)
                cpuL_next = jnp.maximum(
                    cpuL, jnp.where(active, start_b + T_l, -jnp.inf).max())
            W_L = start_b - aL
            L1 = start_b + c_req
            fan_done = L1[:, None] + (kk_r[None, :] + 1.0) * c_fanout
            cpuL2 = L1 + ngf * c_fanout

        with jax.named_scope("relay_pick"):
            # rotating-relay choice.  Fault path: sample uniformly among the
            # group members that are UP at the burst's pacing point (the DES
            # leader gray-lists a dead relay after one timeout and avoids it,
            # so steady-state relay duty falls on the live members) — reduces
            # to the plain floor(u * size) draw when everyone is up.  Static
            # relays are pinned to slot 0 even when down (the DES retries the
            # same dead relay forever in that mode; the round defers
            # identically).
            if faulty:
                tref = L1[0]
                down0 = ((tref >= downF[:, :, 0])
                         & (tref < downF[:, :, 1])).any(-1)   # (F,)
                af = (valid & ~down0).astype(f32)
                # rank among the up members
                rank = seg_cumsum(af, seg_first[0], axis=0) - af
                cnt = jnp.zeros(G, f32).at[grp].add(af)       # (G,) up members
                k_sel = jnp.minimum(jnp.floor(u_rel * cnt[None, :]),
                                    jnp.maximum(cnt - 1.0, 0.0))   # (B, G)
                k_slot = take(k_sel, grp, 1, oh_grp)              # (B, F)
                is_sel = (af > 0)[None, :] & (rank[None, :] == k_slot)
                j_dyn = jnp.zeros((B, G), f32).at[:, grp].add(
                    jnp.where(is_sel, posf[None, :], 0.0))
                j_rel = jnp.where(cell["static_relay"], 0,
                                  j_dyn.astype(jnp.int32))
            else:
                j_rel = jnp.where(cell["static_relay"], 0,
                                  jnp.floor(u_rel * szf).astype(jnp.int32))
            j_rel = jnp.clip(j_rel, 0, jnp.maximum(sizes - 1, 0))
            rel_idx = jnp.clip(gstart + j_rel, 0, F - 1)  # (B, G) flat slots
            oh_rel = (rel_idx[..., None] == jnp.arange(F)) if onehot else None
            j_slot = take(j_rel, grp, 1, oh_grp)            # (B, F)

        with jax.named_scope("relay_fanout"):
            # online rate estimate (EWMA of the L1 pacing interval) -> follower
            # utilization rho and an M/D/1 stochastic-wait floor
            n_act = jnp.maximum(active.sum().astype(f32), 1.0)
            last_L1 = jnp.where(active, L1, -jnp.inf).max()
            dt_ewma = jnp.where(any_active,
                                0.95 * dt_ewma
                                + 0.05 * (last_L1 - t_prev) / n_act, dt_ewma)
            t_prev = jnp.where(any_active, last_L1, t_prev)
            rho = jnp.clip(cell["w_follower"] / jnp.maximum(dt_ewma, 1e-9),
                           0.0, 0.95)
            md1 = rho * w_peer / (2.0 * (1.0 - rho))

            # relay: receive the fanout, re-broadcast to its group peers.
            # Follower CPUs are fluid work-backlog accumulators anchored at L1,
            # the leader's pacing point (monotone over the scan): waits are the
            # outstanding WORK at the node with a fluid drain to the arrival
            # time plus the M/D/1 floor — never a wall-clock reservation.
            # Anchoring at the (late, cross-round out-of-order) arrival times
            # would let one round's pipeline latency masquerade as CPU backlog
            # for the next round and cascade; anchoring at the client issue
            # time t0 would let closed-loop reissue waves masquerade as
            # backlog the leader's serialization actually paces out.
            # LAN batches (reg_lat is 1x1 — a static shape) skip every region
            # gather: all link bases collapse to one scalar.  The WAN's
            # region lookups carry a "regions" scope inside their stage
            lan = reg_lat.shape[0] == 1
            if lan:
                b_Lr = b_rL = reg_lat[0, 0]
                b_rp = b_pr = reg_lat[0, 0]
            else:
                with jax.named_scope("regions"):
                    reg_relay = take(regF, rel_idx, 0, oh_rel)    # (B, G)
                    b_Lr = lut(reg_lat, leader_reg, reg_relay)
                    # per-direction bases: one-way matrices may be asymmetric
                    reg_relay_f = take(reg_relay, grp, 1, oh_grp)
                    b_rp = lut(reg_lat, reg_relay_f, regF[None, :])  # out
                    b_pr = lut(reg_lat, regF[None, :], reg_relay_f)  # back
            arr_rel = fan_done + b_Lr + e_Lr
            if faulty:
                slow_rel = take(slowF, rel_idx, 0, oh_rel)    # (B, G)
                down_rel = take(downF, rel_idx, 0, oh_rel)    # (B, G, W, 2)
                arr_rel = defer(arr_rel + slowL + slow_rel, down_rel)
            B_r = take(cpuF, rel_idx, 0, oh_rel) - L1[:, None]
            W_r = jnp.maximum(B_r + (rho - 1.0) * (arr_rel - L1[:, None]),
                              0.0) + md1
            h = arr_rel + W_r + c_fanout
            is_relay = pos[None, :] == j_slot                 # (B, F)
            peer_mask = valid[None, :] & ~is_relay
            order = (pos[None, :] - (pos[None, :] > j_slot)).astype(f32)
            send_done = take(h, grp, 1, oh_grp) + (order + 1.0) * c_rel
            arr_p = send_done + b_rp + e_rp
            if faulty:
                # relay-out + peer-in slow extras; a down peer serves the
                # relayed message after it recovers (its vote arrives late and
                # simply sorts past the flush threshold if others cover it)
                slow_rel_f = take(slow_rel, grp, 1, oh_grp)
                arr_p = defer(arr_p + slow_rel_f + slowF[None, :], downF)
            W_p = jnp.maximum(cpuF[None, :] - L1[:, None]
                              + (rho - 1.0) * (arr_p - L1[:, None]), 0.0) + md1
            doneP = arr_p + W_p + c_rel + c_repl
            arr_back = doneP + b_pr + e_pr
            if faulty:
                # the returning reply queues at the relay once IT is back up
                win_rel_f = take(down_rel, grp, 1, oh_grp)    # (B,F,W,2)
                arr_back = defer(arr_back + slow_rel_f + slowF[None, :],
                                 win_rel_f)

        with jax.named_scope("relay_acks"):
            # relay FIFO over its reply fan-in: k-th completion via
            # key-sorted arrivals + segmented cumulative max (done_k =
            # max(arr_k, done_{k-1}) + c); each returning reply queues behind
            # the relay's fluid-drained backlog and this round's own sends
            # (relay_free0).  The lexicographic (group, arrival) sort keeps
            # each group's segment block in place with arrivals ascending, so
            # the value at flat slot f is group grp[f]'s pos[f]-th reply.
            relay_free0 = h + npeers.astype(f32)[None, :] * c_rel
            B_rf = take(B_r, grp, 1, oh_grp)
            if kernel == "pallas":
                # rank-counting Pallas kernel: emits each slot's capped segment
                # max directly (the thresh-2 order statistic), no sort needed
                from ..kernels import ops as _kops
                m = _kops.seg_fanin(
                    jnp.where(peer_mask, arr_back, jnp.inf), B_rf,
                    grp, kg_slot, rho - 1.0, md1, c_repl, L1)
                mg = take(m, g0, 1, oh_g0)
                done_g = (kg.astype(f32)[None, :] + 1.0) * c_repl \
                    + jnp.maximum(relay_free0, mg)
            else:
                _, arr_s = lax.sort(
                    (grp_b, jnp.where(peer_mask, arr_back, jnp.inf)),
                    num_keys=2)
                w_fan = jnp.maximum(
                    B_rf + (rho - 1.0) * (arr_s - L1[:, None]), 0.0) + md1
                pref = seg_cummax(arr_s + w_fan - posf[None, :] * c_repl,
                                  seg_first, axis=1)
                done_k = (posf[None, :] + 1.0) * c_repl + jnp.maximum(
                    take(relay_free0, grp, 1, oh_grp), pref)
                done_g = take(done_k, t_idx, 1, oh_t)
            flush = jnp.where((thresh >= 2)[None, :], done_g, relay_free0)
            agg_sent = flush + c_agg
            if not lan:
                with jax.named_scope("regions"):
                    b_rL = lut(reg_lat, reg_relay, leader_reg)    # (B, G)

        with jax.named_scope("commit"):
            # leader FIFO over aggregates; commit at the quorum-completing one
            agg_in = agg_sent + b_rL + e_rL
            if faulty:
                agg_in = defer(agg_in + slow_rel + slowL, downL)
            arr_agg = jnp.where(grp_mask[None, :], agg_in, jnp.inf)
            acks_b = jnp.broadcast_to(acks, (B, G))
            arr_as, acks_s = lax.sort((arr_agg, acks_b), num_keys=1)
            cum = jnp.cumsum(acks_s, axis=1)
            got = 1.0 + cum >= majf
            kstar = jnp.argmax(got, axis=1)
            prefL = lax.cummax(arr_as + W_L[:, None] - kk_r[None, :] * c_agg,
                               axis=1)
            doneL = (kk_r[None, :] + 1.0) * c_agg \
                + jnp.maximum(cpuL2[:, None], prefL)
            commit_done = jnp.where(
                jnp.any(got, axis=1),
                jnp.take_along_axis(doneL, kstar[:, None], axis=1)[:, 0],
                jnp.inf)
            reply_done = commit_done + c_replycl
            t_fin = reply_done + reg_lat[leader_reg, 0] + e_cl[:, 1]
            if faulty:
                t_fin = t_fin + slowL
            if read:
                # leased reads never enter the log: the reply leaves the leader
                # at service completion, and commit_done = inf keeps them out
                # of `committed` and every commit-windowed load
                read_fin = (start_b + w_serve + reg_lat[leader_reg, 0]
                            + e_cl[:, 1])
                commit_done = jnp.where(is_read, jnp.inf, commit_done)
                t_fin = jnp.where(is_read, read_fin, t_fin)

        with jax.named_scope("state"):
            # state updates: follower backlogs grow by the burst's per-node
            # WORK from the anchor (the first active request's pacing point —
            # every round touches every follower, so that is the first
            # toucher)
            act_b = ((active & ~is_read) if read else active)[:, None]
            add_w = (jnp.where(act_b & peer_mask, w_peer, 0.0).sum(axis=0)
                     .at[jnp.where(act_b & grp_mask[None, :], rel_idx, F)]
                     .add(jnp.broadcast_to(relay_work, (B, G)), mode="drop"))
            anchored = jnp.maximum(cpuF, jnp.where(any_active, L1[0], 0.0))
            cpuF = jnp.where(any_active, anchored + add_w, cpuF)
            cpuL = jnp.where(any_active, cpuL_next, cpuL)
            ready = ready.at[cids].set(jnp.where(active, t_fin, jnp.inf))

            # per-node message loads, accumulated over the measurement window
            in_win = active & (commit_done >= warmup) & (commit_done
                                                         <= stop + _DRAIN_S)
            win_b = in_win[:, None]
            loadF = loadF + (jnp.where(win_b & peer_mask, 2.0, 0.0).sum(axis=0)
                             .at[jnp.where(win_b & grp_mask[None, :],
                                           rel_idx, F)]
                             .add(jnp.broadcast_to(2.0 * szf, (B, G)),
                                  mode="drop"))
            loadL = loadL + jnp.where(in_win, 2.0 * ngf + 2.0, 0.0).sum()

            ys = (t_fin - t0, t_fin, commit_done, active)
            if obs:
                # leader-backlog observation: the wait the step's first popped
                # request just experienced at the leader FIFO (= backlog in
                # seconds at its arrival instant), stamped with that arrival
                ys = ys + (jnp.where(any_active, aL[0], jnp.inf), W_L[0])
            if read:
                ys = ys + (is_read,)
        return ((ready, cpuF, cpuL, loadF, loadL, dt_ewma, t_prev),
                ys)

    carry0 = (ready0, jnp.zeros(F, f32), jnp.float32(0.0),
              jnp.zeros(F, f32), jnp.float32(0.0),
              jnp.float32(1.0), jnp.float32(0.0))
    (ready, _, _, loadF, loadL, _, _), ys = \
        lax.scan(step_fn, carry0, jnp.arange(steps))
    with jax.named_scope("summary"):
        lat, t_fin, commit_t, active = ys[:4]
        out = _summarize(lat.reshape(-1), t_fin.reshape(-1),
                         commit_t.reshape(-1), active.reshape(-1), ready,
                         loadF.sum(), loadL, cell, nb=nb)
        if obs:
            t_obs, qlag = ys[4], ys[5]
            ok = jnp.isfinite(t_obs) & (t_obs <= stop + _DRAIN_S)
            tb = jnp.clip(jnp.where(ok, jnp.floor(t_obs / _TL_BUCKET), 0.0)
                          .astype(jnp.int32), 0, nb - 1)
            w = ok.astype(f32)
            qsum = jnp.zeros(nb, f32).at[tb].add(qlag * w)
            qn = jnp.zeros(nb, f32).at[tb].add(w)
            out["leader_backlog_s"] = jnp.where(
                qn > 0, qsum / jnp.maximum(qn, 1.0), 0.0)
            out["leader_backlog_n"] = qn.astype(jnp.int32)
        if read:
            # read/write latency split over the same measurement window the
            # headline latencies use (DES counterpart:
            # Cluster.read_write_split)
            isr = ys[-1].reshape(-1)
            latf, tf = lat.reshape(-1), t_fin.reshape(-1)
            in_lat = active.reshape(-1) & (tf >= cell["warmup"]) \
                & (tf <= cell["stop"])
            rm, wm = in_lat & isr, in_lat & ~isr
            rn, wn = rm.sum(), wm.sum()
            out["read_count"], out["write_count"] = rn, wn
            out["read_mean_s"] = jnp.where(
                rn > 0, jnp.where(rm, latf, 0.0).sum()
                / jnp.maximum(rn.astype(f32), 1.0), jnp.nan)
            out["write_mean_s"] = jnp.where(
                wn > 0, jnp.where(wm, latf, 0.0).sum()
                / jnp.maximum(wn.astype(f32), 1.0), jnp.nan)
            out["read_p99_s"] = _pct(jnp.sort(jnp.where(rm, latf, jnp.inf)),
                                     rn, 0.99)
    return out


# ============================================================= epaxos kernel
def _epaxos_cell(cell, steps: int, kmax: int, nb: int = 0):
    """One grid cell of the EPaxos kernel: random command leader per
    request, PreAccept broadcast to all peers, fast-quorum commit on the
    conflict-free path, ECommit broadcast — plus the conflict/slow-path
    model (ISSUE 5):

    * each request draws its key from the workload distribution (uniform /
      zipfian via the cached CDF / hot-key conflict);
    * a request CONFLICTS when the previous same-key instance's PreAccept
      round is still propagating at our fan-out time (``race[k]``) — then
      peers report divergent deps and the commit takes the slow path: a
      Paxos-accept fan-out + majority fan-in (second sorted-cummax round);
    * execution (and hence the client reply) additionally waits until the
      previous same-key instance's commit is known everywhere
      (``depk[k]``) — the dependency-order execution gate.
    """
    f32 = jnp.float32
    n = cell["reg_nodes"].shape[0]
    reg_nodes = cell["reg_nodes"]
    reg_lat = cell["reg_lat"]
    jitter = cell["jitter"]
    (c_req, c_pa, c_par, c_com, c_replycl, c_acc, c_accr) = [
        cell["costs"][i] for i in range(7)]
    fq = cell["fq"]
    maj = cell["majority"]
    stop, warmup = cell["stop"], cell["warmup"]
    key = cell["key"]
    ids = jnp.arange(n)
    kk = jnp.arange(n, dtype=f32)
    nk = cell["key_cdf"].shape[0]
    nkeysf = cell["n_keys"].astype(f32)
    key_mode = cell["key_mode"]
    crate = cell["conflict_rate"]

    ready0 = jnp.where(jnp.arange(kmax) < cell["k_clients"],
                       _CLIENT_START + _CLIENT_STAGGER * jnp.arange(kmax),
                       jnp.inf).astype(f32)

    def step_fn(carry, i):
        ready, cpu, load, race, depk = carry
        with jax.named_scope("keys"):
            ks = jax.random.split(jax.random.fold_in(key, i), 5)
            cid = jnp.argmin(ready)
            t0 = ready[cid]
            active = t0 < stop

            coord = jax.random.randint(ks[0], (), 0, n)
            e_cl = jax.random.exponential(ks[1], (2,)) * jitter
            e_out = jax.random.exponential(ks[2], (n,)) * jitter
            e_back = jax.random.exponential(ks[3], (n,)) * jitter
            u_key = jax.random.uniform(ks[4], ())

            # per-request key draw from the workload's distribution
            k_uni = jnp.floor(u_key * nkeysf).astype(jnp.int32)
            k_zipf = jnp.searchsorted(cell["key_cdf"], u_key,
                                      side="right").astype(jnp.int32)
            k_conf = jnp.where(
                u_key < crate, 0,
                1 + jnp.floor((u_key - crate) / jnp.maximum(1.0 - crate, 1e-9)
                              * (nkeysf - 1.0)).astype(jnp.int32))
            k = jnp.where(key_mode == 1, k_zipf,
                          jnp.where(key_mode == 2, k_conf, k_uni))
            k = jnp.clip(k, 0, cell["n_keys"] - 1)

        with jax.named_scope("preaccept"):
            coord_reg = reg_nodes[coord]
            b_cl = reg_lat[0, coord_reg]          # clients live in region 0
            b_cp = reg_lat[coord_reg, reg_nodes]  # coord -> peer bases (n,)
            # peer -> coord (asymmetric ok)
            b_pc = reg_lat[reg_nodes, coord_reg]

            # every node's CPU is a fluid work-backlog anchored at t0 (see the
            # group kernel): the command-leader role rotates per request, so
            # wall-clock anchoring would cascade across requests
            aC = t0 + b_cl + e_cl[0]
            W_C = jnp.maximum(cpu[coord] - t0, 0.0)
            L1 = aC + W_C + c_req
            is_peer = ids != coord
            order = (ids - (ids > coord)).astype(f32)
            pa_done = L1 + (order + 1.0) * c_pa
            cpuC2 = L1 + (n - 1) * c_pa

            arr_p = pa_done + b_cp + e_out
            W_p = jnp.maximum(cpu - t0, 0.0)
            doneP = arr_p + W_p + c_pa + c_par
            arr_back = jnp.where(is_peer, doneP + b_pc + e_back, jnp.inf)

            # reply fan-in: the coordinator's backlog partially drains over
            # the round trip (it keeps serving while the round is in flight),
            # so the wait each reply sees decays from W_C with the elapsed
            # time — the 0.5 net-drain rate is calibrated against the fast
            # DES (the node also ingests new work while draining, see
            # tests/test_vectorsim.py)
            arr_s = jnp.sort(arr_back)
            W_fan = jnp.maximum(W_C - 0.5 * (arr_s - L1), 0.0)
            pref = lax.cummax(arr_s + W_fan - kk * c_par)
            done_k = (kk + 1.0) * c_par + jnp.maximum(cpuC2, pref)
            # fast-path commit after fq-1 peer replies (the leader votes
            # itself)
            fast_commit = done_k[jnp.clip(fq - 2, 0, n - 1)]

        with jax.named_scope("conflict"):
            # conflict draw: the previous same-key instance's PreAccept round
            # is still propagating when we fan out -> peers report divergent
            # deps and the coordinator falls back to the Paxos-accept slow
            # path
            slow = active & (L1 < race[k])
            acc_done = fast_commit + (order + 1.0) * c_acc
            cpuC3 = fast_commit + (n - 1) * c_acc
            arr_p2 = acc_done + b_cp + e_out
            doneP2 = arr_p2 + W_p + c_acc + c_accr
            arr_back2 = jnp.where(is_peer, doneP2 + b_pc + e_back, jnp.inf)
            arr_s2 = jnp.sort(arr_back2)
            W_fan2 = jnp.maximum(W_C - 0.5 * (arr_s2 - L1), 0.0)
            pref2 = lax.cummax(arr_s2 + W_fan2 - kk * c_accr)
            done_k2 = (kk + 1.0) * c_accr + jnp.maximum(cpuC3, pref2)
            slow_commit = done_k2[jnp.clip(maj - 2, 0, n - 1)]
            commit_done = jnp.where(slow, slow_commit, fast_commit)

        with jax.named_scope("exec_gate"):
            # dependency-order execution: a same-key successor cannot execute
            # (and answer its client) before the predecessor's commit is known
            # at its coordinator
            exec_done = jnp.maximum(commit_done + (n - 1) * c_com, depk[k])
            reply_done = exec_done + c_replycl
            t_fin = reply_done + reg_lat[coord_reg, 0] + e_cl[1]

        with jax.named_scope("state"):
            slowf = slow.astype(f32)
            anchored = jnp.maximum(cpu, t0)
            coord_work = (c_req + (n - 1) * (c_pa + c_par + c_com) + c_replycl
                          + slowf * (n - 1) * (c_acc + c_accr))
            new_cpu = jnp.where(is_peer,
                                anchored + c_pa + c_par + c_com
                                + slowf * (c_acc + c_accr), cpu)
            new_cpu = new_cpu.at[coord].set(anchored[coord] + coord_work)
            cpu = jnp.where(active, new_cpu, cpu)
            ready = ready.at[cid].set(jnp.where(active, t_fin, jnp.inf))

            # conflict-tracking state: when every peer has processed this
            # request's PreAccept (race), and when its commit is known
            # everywhere (depk — ECommit broadcast plus a one-way hop)
            race_new = jnp.where(is_peer, arr_p + W_p + c_pa, -jnp.inf).max()
            b_prop = (jnp.where(is_peer, b_cp, 0.0).sum()
                      / jnp.maximum(n - 1, 1))
            dep_new = commit_done + (n - 1) * c_com + b_prop + jitter
            race = race.at[k].set(jnp.where(active, race_new, race[k]))
            depk = depk.at[k].set(jnp.where(active, dep_new, depk[k]))

            in_win = active & (commit_done >= warmup) & (commit_done
                                                         <= stop + _DRAIN_S)
            add = jnp.where(is_peer, 3.0 + 2.0 * slowf,
                            (3.0 * n - 1.0) + 2.0 * (n - 1) * slowf)
            load = load + jnp.where(in_win, 1.0, 0.0) * add

        return ((ready, cpu, load, race, depk),
                (t_fin - t0, t_fin, commit_done, active))

    carry0 = (ready0, jnp.zeros(n, f32), jnp.zeros(n, f32),
              jnp.zeros(nk, f32), jnp.zeros(nk, f32))
    (ready, _, load, _, _), (lat, t_fin, commit_t, active) = lax.scan(
        step_fn, carry0, jnp.arange(steps))
    with jax.named_scope("summary"):
        # symmetric protocol: report node 0 as "leader", the rest as followers
        return _summarize(lat, t_fin, commit_t, active, ready,
                          load[1:].sum(), load[0], cell, nb=nb)


# ================================================================== batching
def _resolve_kernel(kind: str) -> str:
    """The platform rule for the reply fan-in: the Pallas kernel for the
    group kernel on a TPU backend, the XLA "lax" path otherwise (the
    epaxos kernel has no grouped fan-in)."""
    return ("pallas" if kind == "group" and jax.default_backend() == "tpu"
            else "lax")


# the fan-in rule both grid entries read; tests swap it to force one path
# (the lax path is the Pallas kernel's oracle)
_fanin = _resolve_kernel


def _cells_fn(batch, steps: int, kmax: int, kind: str, breq: int,
              faulty: bool = False, nb: int = 0, kernel: str = "lax",
              obs: bool = False, read: bool = False):
    """The unjitted whole-batch computation (vmap over cells); shared by
    the single-device jit below and the sharded per-device bodies.  Runs
    only while JAX traces it, so its span marks a retrace."""
    with TraceAnnotation("vectorsim.trace"):
        if kind == "group":
            return jax.vmap(lambda c: _group_cell(c, steps, kmax, breq,
                                                  faulty, nb, kernel,
                                                  obs, read))(batch)
        return jax.vmap(lambda c: _epaxos_cell(c, steps, kmax, nb))(batch)


@functools.partial(jax.jit, static_argnames=("steps", "kmax", "kind",
                                             "breq", "faulty", "nb",
                                             "kernel", "obs", "read"))
def _run_cells(batch, steps: int, kmax: int, kind: str, breq: int,
               faulty: bool = False, nb: int = 0, kernel: str = "lax",
               obs: bool = False, read: bool = False):
    sig = (kind, steps, kmax, breq, faulty, nb, kernel, obs, read) + tuple(
        (k,) + tuple(v.shape) for k, v in sorted(batch.items()))
    _TRACE_COUNTS[sig] = _TRACE_COUNTS.get(sig, 0) + 1
    return _cells_fn(batch, steps, kmax, kind, breq, faulty, nb, kernel,
                     obs, read)


def _pad_spec(configs: Sequence[SimConfig], grid) -> Dict[str, int]:
    """The padded-shape signature a (configs, grid) batch compiles under.
    A sharded run computes this ONCE over the whole grid and passes it to
    every chunk's ``_stack_cells`` so all chunks share one compilation."""
    kind = configs[0].kind
    spec = {
        "nreg": max(c.region_latency.shape[0] for c in configs),
        "kmax": max(k for _, k, _ in grid),
        "wmax": max([c.down.shape[1] for c in configs
                     if c.down is not None] + [1]),
    }
    if kind == "group":
        spec["rmax"] = max(c.rmax for c in configs)
        spec["fmax"] = max(c.n - 1 for c in configs)
        spec["nmax"] = 1
        spec["nkeys_max"] = 1   # the group kernel never samples keys
    else:
        spec["rmax"] = spec["fmax"] = 1
        spec["nmax"] = max(c.n for c in configs)
        spec["nkeys_max"] = max(c.n_keys for c in configs)
    return spec


def _stack_cells(configs: Sequence[SimConfig], grid, duration: float,
                 warmup: float, pad_to: Optional[Dict[str, int]] = None):
    """Stack (config_idx, clients, seed) grid points into one batch dict.

    ``pad_to`` (a ``_pad_spec`` dict, possibly from a larger grid) pins the
    padded shapes so different chunks of one sharded run stay signature-
    compatible with each other."""
    with TraceAnnotation("vectorsim.stack"):
        kind = configs[0].kind
        if any(c.kind != kind for c in configs):
            raise ValueError("cannot mix group and epaxos kernels in one "
                             "batch")
        spec = pad_to or _pad_spec(configs, grid)
        nreg = spec["nreg"]
        kmax = spec["kmax"]
        stop = warmup + duration
        cells: Dict[str, list] = {k: [] for k in (
            "sizes", "thresh", "grp", "pos", "gstart", "regF", "reg_lat",
            "leader_reg", "jitter", "costs",
            "majority", "n_groups", "static_relay", "k_clients", "key", "stop",
            "warmup", "duration", "n_followers", "reg_nodes", "fq",
            "w_follower", "downL", "downF", "slowF", "slowL",
            "key_mode", "n_keys", "conflict_rate", "key_cdf", "read_ratio")}
        wmax = spec["wmax"]
        rmax, fmax = spec["rmax"], spec["fmax"]
        nmax, nkeys_max = spec["nmax"], spec["nkeys_max"]
        if kind == "epaxos" and any(c.n != nmax for c in configs):
            raise ValueError("epaxos batches must share one cluster size")
        for ci, k, seed in grid:
            c = configs[ci]
            sizes = np.zeros(rmax, np.int32)
            thresh = np.zeros(rmax, np.int32)
            # flat group-contiguous follower layout (padding at the tail keeps
            # segment scans confined to real slots)
            grp = np.full(fmax, max(rmax - 1, 0), np.int32)
            pos = np.full(fmax, 1, np.int32)  # non-zero: never a segment start
            gstart = np.zeros(rmax, np.int32)
            regf = np.zeros(fmax, np.int32)
            # fault masks in flat-slot layout (inf-padded = never down)
            downf = np.full((fmax, wmax, 2), np.inf, np.float32)
            slowf = np.zeros(fmax, np.float32)
            downl = np.full((wmax, 2), np.inf, np.float32)
            slowl = np.float32(0.0)
            if kind == "group":
                sizes[:c.rmax] = c.sizes
                thresh[:c.rmax] = c.thresh
                off = 0
                for gi in range(c.rmax):
                    sz = int(c.sizes[gi])
                    grp[off:off + sz] = gi
                    pos[off:off + sz] = np.arange(sz)
                    gstart[gi] = off
                    members = c.members[gi, :sz]
                    regf[off:off + sz] = c.region_of[members]
                    if c.down is not None:
                        downf[off:off + sz, :c.down.shape[1]] = c.down[members]
                    if c.slow is not None:
                        slowf[off:off + sz] = c.slow[members]
                    off += sz
                gstart[c.rmax:] = off
                if c.down is not None:
                    downl[:c.down.shape[1]] = c.down[0]
                if c.slow is not None:
                    slowl = np.float32(c.slow[0])
            rl = np.zeros((nreg, nreg), np.float64)
            nr = c.region_latency.shape[0]
            rl[:nr, :nr] = c.region_latency
            cells["sizes"].append(sizes)
            cells["thresh"].append(thresh)
            cells["grp"].append(grp)
            cells["pos"].append(pos)
            cells["gstart"].append(gstart)
            cells["regF"].append(regf)
            cells["downL"].append(downl)
            cells["downF"].append(downf)
            cells["slowF"].append(slowf)
            cells["slowL"].append(slowl)
            cells["reg_lat"].append(rl.astype(np.float32))
            cells["leader_reg"].append(np.int32(c.region_of[0]))
            cells["jitter"].append(np.float32(c.jitter))
            if kind == "group":
                order = ("c_req", "c_fanout", "c_rel", "c_repl", "c_agg",
                         "c_replycl")
            else:
                order = ("c_req", "c_pa", "c_par", "c_com", "c_replycl",
                         "c_acc", "c_accr")
            cells["costs"].append(np.asarray([c.costs[o] for o in order],
                                             np.float32))
            cells["key_mode"].append(np.int32(c.key_mode))
            cells["n_keys"].append(
                np.int32(c.n_keys if kind == "epaxos" else 1))
            cells["conflict_rate"].append(np.float32(c.conflict_rate))
            cdf = np.ones(nkeys_max, np.float32)
            if kind == "epaxos" and c.key_cdf is not None:
                cdf[:len(c.key_cdf)] = np.asarray(c.key_cdf, np.float32)
            cells["key_cdf"].append(cdf)
            cells["majority"].append(np.int32(c.majority))
            cells["n_groups"].append(np.int32(int((c.sizes > 0).sum())))
            cells["static_relay"].append(np.bool_(c.static_relay))
            cells["k_clients"].append(np.int32(k))
            cells["key"].append(np.asarray(
                jax.random.PRNGKey(int(seed) * 1_000_003 + ci)))
            cells["stop"].append(np.float32(stop))
            cells["warmup"].append(np.float32(warmup))
            cells["duration"].append(np.float32(duration))
            cells["n_followers"].append(np.int32(c.n - 1))
            if kind == "group":
                szs = c.sizes[c.sizes > 0].astype(float)
                wf = (len(szs) * (c.costs["c_fanout"] + c.costs["c_agg"])
                      + 2.0 * float((szs - 1).sum())
                      * (c.costs["c_rel"] + c.costs["c_repl"])) \
                    / max(c.n - 1, 1)
                # leased reads add no follower work: the utilization estimate
                # sees per-op work scaled to the write fraction
                wf *= 1.0 - c.read_ratio
            else:
                wf = 0.0
            cells["w_follower"].append(np.float32(wf))
            cells["read_ratio"].append(np.float32(c.read_ratio))
            cells["reg_nodes"].append(
                np.asarray(c.region_of[:nmax] if kind == "epaxos"
                           else np.zeros(1), np.int32))
            cells["fq"].append(np.int32(fast_quorum(c.n)))
        batch = {k: np.stack(v) for k, v in cells.items()}
        return batch, kind, kmax


def simulate_grid(configs: Sequence[SimConfig], grid, duration: float,
                  warmup: float, steps: Optional[int] = None,
                  obs: bool = False) -> Dict[str, np.ndarray]:
    """Run every (config_idx, clients, seed) grid point in ONE jitted call.

    Returns dict of per-cell arrays (throughput, median_s, p99_s, committed,
    m_leader, m_follower, ...).  Step budgets are per cell: the first call
    uses the grid max (so unexhausted grids stay one compiled call), and
    when the optimistic rate bound underestimates some cells, ONLY the
    exhausted subset re-runs with a doubled budget — finished cells keep
    their first-pass results, which are bit-identical to what a full-grid
    retry would produce (extra scan steps past the stop time are no-ops).
    ``out["steps"]`` records each cell's final budget.  Fault-mask configs
    add per-cell completion timelines (``_TL_BUCKET`` buckets).

    ``obs=True`` (group kernel only) adds the per-cell leader-backlog
    series (``leader_backlog_s`` / ``leader_backlog_n``; see
    ``_group_cell``) on the same buckets.
    """
    with TraceAnnotation("vectorsim.grid") as span:
        batch, _, kmax = _stack_cells(configs, grid, duration, warmup)
        steps, plan = _plan(configs, grid, duration, warmup, steps, kmax,
                            obs)
        out, passes = _passes(lambda b, s: _run_cells(b, s, *plan), batch,
                              steps, plan.breq, len(grid), 1)
        span.set_metadata(passes=passes,
                          **_region_count(batch["reg_lat"].shape[-1]))
    return out


class _Plan(NamedTuple):
    """A grid call's static program arguments, in ``_run_cells`` order."""
    kmax: int
    kind: str
    breq: int
    faulty: bool
    nb: int
    kernel: str
    obs: bool
    read: bool


def _plan(configs: Sequence[SimConfig], grid, duration: float,
          warmup: float, steps: Optional[int], kmax: int,
          obs: bool = False) -> Tuple[int, _Plan]:
    """The decisions both grid entries share: the first request budget
    (the grid max of the optimistic rate bound unless ``steps`` is given,
    capped at ``_MAX_STEPS``) and the program's static arguments (mode
    flags, burst, fan-in)."""
    kind = configs[0].kind
    if obs and kind != "group":
        raise ValueError("obs timelines are group-kernel only — the "
                         "epaxos kernel has no single-leader FIFO to "
                         "observe")
    faulty = any(c.down is not None or c.slow is not None for c in configs)
    read = any(c.read_ratio > 0.0 for c in configs)
    nb = (int(np.ceil((warmup + duration + _DRAIN_S) / _TL_BUCKET)) + 1
          if (faulty or obs) else 0)
    if steps is None:
        # requests are only issued inside [0, stop); the rate bound is
        # optimistic, and the exhausted-retry loop is the safety net
        with TraceAnnotation("vectorsim.budget"):
            rate = max(_estimate_rate(configs[ci], k) for ci, k, _ in grid)
        steps = int(rate * (warmup + duration) * 1.15) + kmax + 64
    # the group kernel pops `breq` requests per scan step
    breq = min(8, kmax) if kind == "group" else 1
    return min(steps, _MAX_STEPS), _Plan(kmax, kind, breq, faulty, nb,
                                         _fanin(kind), obs, read)


def _passes(run, batch, steps: int, breq: int, real: int,
            pad: int) -> Tuple[Dict[str, np.ndarray], int]:
    """The exhausted-retry loop: one pass over ``batch``, then ONLY the
    exhausted cells re-run at a doubled budget until none of the first
    ``real`` cells is exhausted or the budget reaches ``_MAX_STEPS``.  A
    retry subset is padded to a multiple of ``pad`` (the sharded runner's
    device count) by repeating its cells.  Returns the outputs, with each
    cell's final budget under ``"steps"``, and the number of passes."""
    out = _pass(run, batch, steps, breq)
    steps_arr = np.full(len(batch["key"]), steps, np.int32)
    if out["exhausted"].any():
        out = {k: np.array(v) for k, v in out.items()}  # writable
    passes = 1
    while out["exhausted"][:real].any() and steps < _MAX_STEPS:
        passes += 1
        steps = min(steps * 2, _MAX_STEPS)
        idx = np.nonzero(out["exhausted"])[0]
        with TraceAnnotation("vectorsim.retry"):
            ridx = np.resize(idx, -(-len(idx) // pad) * pad)
            sub = {k: v[ridx] for k, v in batch.items()}
            for k, v in _pass(run, sub, steps, breq, retry=True).items():
                out[k][idx] = v[:len(idx)]
        steps_arr[idx] = steps
    out["steps"] = steps_arr
    return out, passes


def _region_count(nreg: int) -> Dict[str, int]:
    """The ``regions`` counter of a grid span: the grid's largest region
    count, written only where the grid spans more than one region."""
    return {"regions": nreg} if nreg > 1 else {}


def _pass(run, batch, steps: int, breq: int,
          retry: bool = False) -> Dict[str, np.ndarray]:
    """One pass of the scan over ``batch`` at a budget of ``steps``
    requests: ``run`` looks up the compiled program, copies the batch to
    the device and enqueues it; the readback waits for the device and
    copies the outputs out.  The run span of an exhausted-retry pass also
    counts the ``cells`` it re-runs."""
    scan_steps = -(-steps // breq)
    meta = {"scan_steps": scan_steps}
    if retry:
        meta["cells"] = len(batch["key"])
    with TraceAnnotation("vectorsim.run", **meta):
        out = run(batch, scan_steps)
    with TraceAnnotation("vectorsim.readback") as span:
        out = {k: np.asarray(v) for k, v in out.items()}
        span.set_metadata(exhausted=int(out["exhausted"].sum()))
    return out


# ================================================================= sharding
# compiled sharded runners, keyed by the full static signature (shapes,
# step budget, device count) — chunks of one sharded run hit the same
# entry, so compile cost amortizes across the whole grid
_SHARD_CACHE: Dict[tuple, object] = {}


def _run_cells_sharded(batch, steps: int, kmax: int, kind: str, breq: int,
                       faulty: bool, nb: int, kernel: str, obs: bool,
                       read: bool, devices):
    """One chunk through the device-sharded runner.  The cell axis (every
    leaf's leading axis) is split evenly across ``devices`` — cell count
    must be a multiple of the device count.  Inputs are DONATED: chunked
    callers stream results to host, so device memory stays bounded by one
    chunk regardless of grid size."""
    D = len(devices)
    shapes = tuple((k,) + tuple(v.shape) + (str(np.asarray(v).dtype),)
                   for k, v in sorted(batch.items()))
    sig = (kind, steps, kmax, breq, faulty, nb, kernel, obs, read, D) + shapes
    fn = _SHARD_CACHE.get(sig)
    if fn is None:
        def body(b):
            return _cells_fn(b, steps, kmax, kind, breq, faulty, nb,
                             kernel, obs, read)
        mesh = Mesh(np.asarray(devices), ("cells",))
        fn = jax.jit(jax.shard_map(body, mesh=mesh,
                                   in_specs=PartitionSpec("cells"),
                                   out_specs=PartitionSpec("cells"),
                                   check_vma=False),
                     donate_argnums=0)
        _SHARD_CACHE[sig] = fn
    with warnings.catch_warnings():
        # scalar per-cell inputs can never be reused for the (bigger)
        # outputs; the donation of the large mask/key arrays is what counts
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        return fn(batch)


def simulate_grid_sharded(configs: Sequence[SimConfig], grid,
                          duration: float, warmup: float, *,
                          steps: Optional[int] = None, chunk: int = 4096,
                          devices=None) -> Dict[str, np.ndarray]:
    """``simulate_grid`` scaled out: the cell grid is partitioned across
    devices (``jax.shard_map``) and dispatched in fixed-size chunks whose
    inputs are donated, so device memory is bounded by one chunk and one
    compilation serves every chunk (the padded-shape signature is pinned
    grid-wide via ``_pad_spec``).

    On a CPU host, multi-device execution is exercised via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before the
    process imports jax); on a TPU host the same call shards over
    ``jax.devices()``.  Per-cell results are bit-identical to
    single-device ``simulate_grid`` (cells are independent vmap lanes).

    Returns the ``simulate_grid`` dict plus ``out["sharding"]``: device
    count, fan-in taken, chunk size, and per-chunk {cells, wall_s, steps}
    — the stream the megagrid study and the bench schema consume.
    """
    with TraceAnnotation("vectorsim.grid") as span:
        devices = list(devices if devices is not None else jax.devices())
        D = len(devices)
        chunk = max(chunk - chunk % D, D)
        spec = _pad_spec(configs, grid)
        steps, plan = _plan(configs, grid, duration, warmup, steps,
                            spec["kmax"])
        n_cells = len(grid)
        out: Dict[str, np.ndarray] = {}
        meta = []
        passes = 0
        for lo in range(0, n_cells, chunk):
            part = list(grid[lo:lo + chunk])
            real = len(part)
            part += [part[-1]] * (chunk - real)   # keep one static shape
            with TraceAnnotation("vectorsim.chunk"):
                batch, _, _ = _stack_cells(configs, part, duration, warmup,
                                           pad_to=spec)
                t0 = time.perf_counter()
                cout, p = _passes(
                    lambda b, s: _run_cells_sharded(b, s, *plan, devices),
                    batch, steps, plan.breq, real, D)
                wall = time.perf_counter() - t0
            passes += p
            for k, v in cout.items():
                if k not in out:
                    out[k] = np.empty((n_cells,) + v.shape[1:], v.dtype)
                out[k][lo:lo + real] = v[:real]
            meta.append({"cells": real, "wall_s": wall,
                         "steps": int(cout["steps"][:real].max())})
        out["sharding"] = {"devices": D, "kernel": plan.kernel,
                           "chunk": chunk, "chunks": meta}
        span.set_metadata(passes=passes, **_region_count(spec["nreg"]))
    return out


def simulate_scenario(protocol: str, n: int, *, pig=None, topo=None,
                      workload=None, clients: Sequence[int] = (60,),
                      seeds: Sequence[int] = (0,), duration: float = 0.6,
                      warmup: float = 0.3, leader_timeout: float = 50e-3,
                      masks: Optional[Dict[str, np.ndarray]] = None,
                      batch_m: int = 1,
                      obs: bool = False) -> List[dict]:
    """One scenario's full clients x seeds grid in one compiled call.

    Returns one dict per (clients, seed) in ``runner`` unit order, carrying
    the same measurement fields as a DES ``Cluster.measure`` run.

    ``retry_risk`` marks cells whose p99 latency reaches the leader timeout:
    there the real protocol starts re-proposing slots (extra load the
    timeout-free batch model does not simulate), so DES throughput can
    collapse below the batch prediction — treat those cells as the model's
    validity boundary, not as measurements.  (Fault-mask runs routinely
    trip it: a deferred commit's latency spans the down-window by design.)

    ``masks`` enables the fault path (``FaultPlan.to_masks``); fault units
    additionally carry a completion ``timeline`` in the DES extras format.

    ``batch_m`` > 1 runs the leader-batching model: every ``batch_m``
    clients share one slot (one kernel lane carries a whole batch, with the
    per-batch cost reparameterization of ``build_config``), so client
    counts must divide evenly; throughput/count/committed scale back up by
    m, and latencies are corrected by the mean reply-serialization rank
    ((m-1)/2 per-reply CPU slots — the model charges every sub-command the
    LAST reply's completion).  This models saturated full batches; the
    partial-batch `max_delay` regime is DES-authoritative.  Pipelined slot
    occupancy is inherent here: the Lindley-chain leader FIFO admits new
    slots while earlier ones are in flight, i.e. the DES default
    ``pipeline_depth=0`` (unbounded); finite-depth throttles are
    DES-authoritative too.

    ``obs=True`` (group kernel only) adds a batch-side observability
    extra to every unit: the leader-backlog series sampled at request
    arrivals (mean queueing wait per ``_TL_BUCKET`` bucket + sample
    counts) — the counterpart of the DES timeline sampler's queue-depth
    gauges.  Full span tracing is DES-only.
    """
    cfg = build_config(protocol, n, pig=pig, topo=topo, workload=workload,
                       masks=masks, batch_m=batch_m)
    m = int(batch_m)
    if m > 1:
        for k in clients:
            if int(k) % m:
                raise ValueError(f"clients={k} not divisible by "
                                 f"batch_m={m}: one kernel lane carries a "
                                 f"whole batch of {m} clients")
    grid = [(0, int(k) // m, int(s)) for k in clients for s in seeds]
    out = simulate_grid([cfg], grid, duration, warmup, obs=obs)
    # mean reply rank correction (seconds); 0 when unbatched
    lat_adj = 0.0 if m == 1 else (m - 1) / 2.0 * (cfg.costs["c_replycl"] / m)
    units = []
    kidx = [int(k) for k in clients for _ in seeds]
    sidx = [int(s) for _ in clients for s in seeds]
    for i, (k, s) in enumerate(zip(kidx, sidx)):
        u = {
            "retry_risk": bool(out["p99_s"][i] - lat_adj >= leader_timeout),
            "clients": k, "seed": s,
            "throughput": float(out["throughput"][i]) * m,
            "mean_ms": float(out["mean_s"][i] - lat_adj) * 1e3,
            "median_ms": float(out["median_s"][i] - lat_adj) * 1e3,
            "p25_ms": float(out["p25_s"][i] - lat_adj) * 1e3,
            "p75_ms": float(out["p75_s"][i] - lat_adj) * 1e3,
            "p99_ms": float(out["p99_s"][i] - lat_adj) * 1e3,
            "count": int(out["count"][i]) * m,
            "committed": int(out["committed"][i]) * m,
            "leader_msgs_per_op": float(out["m_leader"][i]) / m,
            "follower_msgs_per_op": float(out["m_follower"][i]) / m,
            "exhausted": bool(out["exhausted"][i]),
        }
        if "timeline" in out:
            u["timeline"] = {"bucket_s": _TL_BUCKET,
                             "counts": out["timeline"][i].tolist()}
        if "leader_backlog_s" in out:
            u["obs"] = {"leader_backlog": {
                "bucket_s": _TL_BUCKET,
                "mean_ms": [round(float(v) * 1e3, 6)
                            for v in out["leader_backlog_s"][i]],
                "n": out["leader_backlog_n"][i].tolist()}}
        if "read_count" in out:
            # leased-read split (DES counterpart: Cluster.read_write_split)
            u["rw"] = {
                "reads": int(out["read_count"][i]),
                "writes": int(out["write_count"][i]),
                "read_mean_ms": float(out["read_mean_s"][i]) * 1e3,
                "write_mean_ms": float(out["write_mean_s"][i]) * 1e3,
                "read_p99_ms": float(out["read_p99_s"][i]) * 1e3,
            }
        units.append(u)
    return units
