"""Plain reference of the batch model's EPaxos round on a LAN, with the
hot-key conflict / slow-path and dependency-order execution model.

A straightforward NumPy re-statement of the request-level model, written
from the model's description and the configuration file alone: no import
of the program, no array it made.  Each request picks a random command
leader, PreAccepts to every peer, commits on the fast quorum unless the
previous instance of its key is still propagating (then a Paxos-accept
round to a majority follows), and executes once its key's predecessor is
committed everywhere.  Every FIFO queue is walked one arrival at a time.

It computes in the dtype it is given: float64 is the reference, a lower
dtype (bfloat16) the control.  The per-request random draws are inputs,
regenerated from the seed with ``jax.random`` (see :func:`draws`).
"""
from __future__ import annotations

import functools

import numpy as np

from bench.references.group_lan import (CLIENT_STAGGER, CLIENT_START, DRAIN_S,
                                       argsort, check_dtype, sort,
                                       summarize)

BURST = 1       # one request per scan step
BLOCK = 1024    # requests whose draws are made in one call


def point_params(cfg: dict, point: dict) -> dict:
    """Per-message CPU costs (PreAccept and friends carry the O(N)
    dependency term) and the key distribution of one conflict point."""
    n = int(cfg["n"])
    cm, wire = cfg["cost_model"], cfg["wire"]
    hdr = float(wire["header_bytes"])
    wl = dict(cfg["workload"], **point.get("workload", {}))
    wf, payload = float(wl["write_fraction"]), float(wl["payload_bytes"])
    cmd = 16.0 + wf * payload
    base, pb = float(cm["base"]), float(cm["per_byte"])
    dep = float(cm["epaxos_extra_per_node"]) * n

    def cost(nbytes):
        return base + pb * nbytes

    pa = cost(hdr + cmd + 12.0 + 8.0 * n) + dep
    costs = dict(c_req=cost(hdr + cmd), c_pa=pa,
                 c_par=cost(hdr + 12.0 + 8.0 * n) + dep, c_com=pa,
                 c_replycl=cost(hdr + 8.0 + (1.0 - wf) * payload),
                 c_acc=pa, c_accr=cost(hdr + 16.0))
    if wl.get("key_dist", "uniform") not in ("uniform", "conflict"):
        raise ValueError("epaxos reference: uniform or conflict keys only")
    crate = (float(wl["conflict_rate"]) if wl.get("key_dist") == "conflict"
             else None)
    fq = (3 * n) // 4 + (1 if (3 * n) % 4 else 0)
    return dict(n=n, costs=costs, n_keys=int(wl["n_keys"]), crate=crate,
                fast_quorum=fq, majority=n // 2 + 1,
                base=float(cfg["topology"]["base_latency"]),
                jitter=float(cfg["topology"]["jitter"]),
                warmup=float(cfg["warmup_s"]),
                duration=float(cfg["duration_s"]))


def draws(keys, first: int, count: int, n: int):
    """Draws for requests [first, first + count) of each key: per request,
    split(fold_in(key, i), 5) gives the command leader (randint in
    [0, n)), two client-hop, n outbound and n inbound Exponential(1)
    jitters, and one Uniform for the key."""
    steps = np.arange(first, first + count, dtype=np.int32)
    return tuple(np.asarray(x) for x in _draw_fn(n)(np.asarray(keys), steps))


@functools.lru_cache(maxsize=None)
def _draw_fn(n: int):
    import jax

    def one(key, i):
        ks = jax.random.split(jax.random.fold_in(key, i), 5)
        return (jax.random.randint(ks[0], (), 0, n),
                jax.random.exponential(ks[1], (2,)),
                jax.random.exponential(ks[2], (n,)),
                jax.random.exponential(ks[3], (n,)),
                jax.random.uniform(ks[4], ()))

    return jax.jit(jax.vmap(jax.vmap(one, (None, 0)), (0, None)))


def _fifo(arrivals, free, waits, cost, upto: int):
    """A FIFO server that is busy until ``free`` and serves replies in
    arrival order, each after its wait; returns the time the first
    ``upto + 1`` replies are done."""
    done = free
    for j in range(upto + 1):
        done = np.maximum(done, arrivals[:, j] + waits[:, j]) + cost
    return done


def simulate(cfg: dict, point: dict, cells, grid_points, kmax: int,
             dt=np.float64, max_steps: int = 0) -> dict:
    """Run every cell (clients, key) of one conflict point to the end of
    its client window, one request per step (``grid_points`` and ``kmax``
    change nothing here).  The run stops early after ``max_steps`` steps
    when that is set, and otherwise after the most steps the clients
    could need (every request at least two one-way hops)."""
    import jax

    p = point_params(cfg, point)
    C, n = len(cells), p["n"]
    c = {k: dt(v) for k, v in p["costs"].items()}
    base, jit = dt(p["base"]), dt(p["jitter"])
    warmup, duration = dt(p["warmup"]), dt(p["duration"])
    stop = warmup + duration
    nk = p["n_keys"]
    nm1 = dt(n - 1)
    zero, half = dt(0), dt(0.5)
    kcl = np.asarray([k for k, _ in cells])
    K = int(kcl.max())
    kidx = np.arange(K)
    ready = np.where(kidx[None, :] < kcl[:, None],
                     dt(CLIENT_START) + dt(CLIENT_STAGGER) * kidx.astype(dt),
                     dt(np.inf)).astype(dt)
    cpu = np.zeros((C, n), dt)
    load = np.zeros((C, n), dt)
    race = np.zeros((C, nk), dt)     # every peer has seen key k's PreAccept
    depk = np.zeros((C, nk), dt)     # key k's last commit known everywhere
    ids = np.arange(n)
    rows = np.arange(C)
    keys = np.stack([np.asarray(jax.random.PRNGKey(kk)) for _, kk in cells])
    rec = []
    step = 0
    max_steps = max_steps or int(K * float(stop) / (2 * p["base"])) + 1
    while (ready < stop).any() and step < max_steps:
        if step % BLOCK == 0:
            blk = draws(keys, step, BLOCK, n)
        coord, e_cl, e_out, e_back, u_key = (x[:, step % BLOCK] for x in blk)
        step += 1
        e_cl = e_cl.astype(dt) * jit
        e_out = e_out.astype(dt) * jit
        e_back = e_back.astype(dt) * jit
        if p["crate"] is None:
            k = np.floor(u_key * np.float32(nk)).astype(np.int64)
        else:
            cr = np.float32(p["crate"])
            hot = u_key < cr
            rest = np.floor((u_key - cr) / np.maximum(np.float32(1.0) - cr,
                                                      np.float32(1e-9))
                            * np.float32(nk - 1)).astype(np.int64)
            k = np.where(hot, 0, 1 + rest)
        k = np.clip(k, 0, nk - 1)

        cid = argsort(ready)[:, 0]
        t0 = ready[rows, cid]
        active = t0 < stop

        # command leader: ingest after its backlog drains to t0
        aC = t0 + base + e_cl[:, 0]
        W_C = np.maximum(cpu[rows, coord] - t0, zero)
        L1 = aC + W_C + c["c_req"]
        peer = ids[None, :] != coord[:, None]
        send_1 = (ids[None, :] - (ids[None, :] > coord[:, None])
                  + 1).astype(dt)
        arr_p = L1[:, None] + send_1 * c["c_pa"] + base + e_out
        W_p = np.maximum(cpu - t0[:, None], zero)
        arr_back = np.where(peer, arr_p + W_p + c["c_pa"] + c["c_par"]
                            + base + e_back, dt(np.inf))

        # PreAccept replies queue at the command leader, whose backlog
        # drains at half rate while the round is in flight
        a1 = sort(arr_back)
        w1 = np.maximum(W_C[:, None] - half * (a1 - L1[:, None]), zero)
        fast = _fifo(a1, L1 + nm1 * c["c_pa"], w1, c["c_par"],
                     p["fast_quorum"] - 2)

        # conflict: key k's previous PreAccept is still propagating
        slow = active & (L1 < race[rows, k])
        arr_p2 = fast[:, None] + send_1 * c["c_acc"] + base + e_out
        arr_back2 = np.where(peer, arr_p2 + W_p + c["c_acc"] + c["c_accr"]
                             + base + e_back, dt(np.inf))
        a2 = sort(arr_back2)
        w2 = np.maximum(W_C[:, None] - half * (a2 - L1[:, None]), zero)
        slow_commit = _fifo(a2, fast + nm1 * c["c_acc"], w2, c["c_accr"],
                            p["majority"] - 2)
        commit = np.where(slow, slow_commit, fast)

        # execute after the key's predecessor is committed everywhere
        execd = np.maximum(commit + nm1 * c["c_com"], depk[rows, k])
        t_fin = execd + c["c_replycl"] + base + e_cl[:, 1]

        slowf = slow.astype(dt)
        anch = np.maximum(cpu, t0[:, None])
        new = anch + c["c_pa"] + c["c_par"] + c["c_com"] \
            + slowf[:, None] * (c["c_acc"] + c["c_accr"])
        lead = (anch[rows, coord] + c["c_req"]
                + nm1 * (c["c_pa"] + c["c_par"] + c["c_com"]) + c["c_replycl"]
                + slowf * nm1 * (c["c_acc"] + c["c_accr"]))
        new[rows, coord] = lead
        cpu = np.where(active[:, None], new, cpu)
        ready[rows, cid] = np.where(active, t_fin, dt(np.inf))

        seen = np.where(peer, arr_p + W_p + c["c_pa"], dt(-np.inf)).max(axis=1)
        known = commit + nm1 * c["c_com"] + base + jit
        race[rows, k] = np.where(active, seen, race[rows, k])
        depk[rows, k] = np.where(active, known, depk[rows, k])

        in_win = active & (commit >= warmup) & (commit <= stop + dt(DRAIN_S))
        per_peer = dt(3) + dt(2) * slowf
        per_lead = dt(3 * n - 1) + dt(2) * nm1 * slowf
        add = np.where(peer, per_peer[:, None], per_lead[:, None])
        load = load + np.where(in_win[:, None], add, zero)
        check_dtype(dt, ready, cpu, load, race, depk, t_fin, commit)
        rec.append((t0[:, None], t_fin[:, None], commit[:, None],
                    active[:, None]))
    return summarize(rec, load[:, 1:].sum(axis=1), load[:, 0], n - 1,
                     warmup, stop, duration, step)
