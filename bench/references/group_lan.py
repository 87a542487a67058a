"""Plain reference of the batch model's Paxos / PigPaxos round on a LAN.

A straightforward NumPy re-statement of the request-level model the batch
backend simulates (one leader, follower relay groups, closed-loop clients,
FIFO CPU queues), written from the model's description and the
configuration file alone: no import of the program, no array it made.
Where the program uses closed forms (cumulative max over a burst, sort +
segmented cumulative max or a rank-counting kernel for the relay fan-in),
this file walks each FIFO queue one arrival at a time.

It computes in the dtype it is given: float64 is the reference, and a
lower dtype (bfloat16) is the control that the comparison must reject.
The per-step random draws are inputs, not model: they are regenerated
here from the seed with ``jax.random`` in the layout the model consumes
(see :func:`draws`).
"""
from __future__ import annotations

import functools
import math

import numpy as np

DRAIN_S = 0.2           # commits count up to stop + DRAIN_S
CLIENT_START = 20e-3    # first client issues at 20 ms ...
CLIENT_STAGGER = 1e-4   # ... and client c at 20 ms + c * 0.1 ms
BURST = 8               # requests popped per scan step (fewer clients: all)
BLOCK = 256             # scan steps whose draws are made in one call


def point_params(cfg: dict, point: dict) -> dict:
    """Groups, thresholds and per-message CPU costs of one protocol point,
    from the configuration's cost model and wire sizes."""
    n = int(cfg["n"])
    cm, wire = cfg["cost_model"], cfg["wire"]
    hdr = float(wire["header_bytes"])
    wl = dict(cfg["workload"], **point.get("workload", {}))
    wf, payload = float(wl["write_fraction"]), float(wl["payload_bytes"])
    cmd = 16.0 + wf * payload
    w_req, w_p2a, w_p2b = hdr + cmd, hdr + 16.0 + cmd, hdr
    w_reply = hdr + 8.0 + (1.0 - wf) * payload

    def cost(nbytes):
        return float(cm["base"]) + float(cm["per_byte"]) * nbytes

    followers = list(range(1, n))
    maj = n // 2 + 1
    if point["protocol"] == "paxos":
        groups = [[f] for f in followers]
        thresh = [1] * len(groups)
        costs = dict(c_req=cost(w_req), c_fanout=cost(w_p2a), c_rel=0.0,
                     c_repl=0.0, c_agg=cost(w_p2b), c_replycl=cost(w_reply))
        static = True
    elif point["protocol"] == "pigpaxos":
        pig = point["pig"]
        r = max(1, min(int(pig["n_groups"]), len(followers)))
        groups = [followers[g::r] for g in range(r)]       # round robin
        # PRC: each group waits for all but `prc` members, raised round
        # robin until the groups together hold a majority with the leader
        req = [max(1, len(g) - int(pig["prc"])) for g in groups]
        i = 0
        while sum(req) < maj - 1 and i <= 4 * len(req):
            if req[i % len(req)] < len(groups[i % len(req)]):
                req[i % len(req)] += 1
            i += 1
        thresh = [min(q, len(g)) for q, g in zip(req, groups)]
        wrap = hdr + 8.0 + w_p2a
        costs = dict(c_req=cost(w_req), c_fanout=cost(wrap),
                     c_rel=cost(wrap), c_repl=cost(hdr + 8.0 + w_p2b),
                     c_agg=cost(hdr + 16.0), c_replycl=cost(w_reply))
        static = not bool(pig.get("rotate_relays", True))
    else:
        raise ValueError(f"group reference: unknown protocol "
                         f"{point['protocol']!r}")
    return dict(n=n, sizes=[len(g) for g in groups], thresh=thresh,
                majority=maj, static=static, costs=costs,
                base=float(cfg["topology"]["base_latency"]),
                jitter=float(cfg["topology"]["jitter"]),
                warmup=float(cfg["warmup_s"]),
                duration=float(cfg["duration_s"]))


def pad_dims(cfg: dict, points) -> dict:
    """Shapes of the per-step draws: the widest group count and follower
    count over every point that shares one grid call."""
    gmax = max(len(point_params(cfg, p)["sizes"]) for p in points)
    return {"groups": gmax, "followers": int(cfg["n"]) - 1}


def draws(keys, first: int, count: int, burst: int, pad: dict):
    """Exponential(1) hop jitters and Uniform relay picks for steps
    [first, first + count) of each key: per step, split(fold_in(key, i))
    into (k1, k2); k1 gives (burst, 2 + 2G + 2F) exponentials laid out as
    [client out, client back, leader->relay (G), relay->leader (G),
    relay->peer (F), peer->relay (F)], k2 gives (burst, G) uniforms."""
    steps = np.arange(first, first + count, dtype=np.int32)
    e, u = _draw_fn(burst, pad["groups"], pad["followers"])(
        np.asarray(keys), steps)
    return np.asarray(e), np.asarray(u)


@functools.lru_cache(maxsize=None)
def _draw_fn(burst: int, G: int, F: int):
    import jax

    def one(key, i):
        k1, k2 = jax.random.split(jax.random.fold_in(key, i))
        return (jax.random.exponential(k1, (burst, 2 + 2 * G + 2 * F)),
                jax.random.uniform(k2, (burst, G)))

    return jax.jit(jax.vmap(jax.vmap(one, (None, 0)), (0, None)))


def simulate(cfg: dict, point: dict, cells, grid_points, kmax: int,
             dt=np.float64, max_steps: int = 0) -> dict:
    """Run every cell (clients, key) of one protocol point to the end of
    its client window; returns per-cell results like the batch backend's
    summary (count, committed, median_s, p99_s, m_leader, m_follower).
    ``grid_points`` (every point of the call) and ``kmax`` (its largest
    client count) fix the draws' layout and the burst size.  The run
    stops early after ``max_steps`` steps when that is set, and otherwise
    after the most steps the clients could need (every request at least
    two one-way hops)."""
    import jax

    p = point_params(cfg, point)
    pad = pad_dims(cfg, grid_points)
    B = min(BURST, kmax)
    C = len(cells)
    sizes = np.asarray(p["sizes"])
    G, S, F = len(sizes), int(sizes.max()), p["n"] - 1
    Gp, Fp = pad["groups"], pad["followers"]
    c = {k: dt(v) for k, v in p["costs"].items()}
    base, jit = dt(p["base"]), dt(p["jitter"])
    warmup, duration = dt(p["warmup"]), dt(p["duration"])
    stop = warmup + duration
    gstart = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    posn = np.arange(S)
    member = posn[None, :] < sizes[:, None]                      # (G, S)
    slot = np.where(member, gstart[:, None] + posn[None, :], F)  # F = none
    thresh = np.asarray(p["thresh"])
    kcap = np.maximum(thresh - 2, 0)
    sizes_d = sizes.astype(dt)
    ng = dt(G)
    T_l = c["c_req"] + ng * (c["c_fanout"] + c["c_agg"]) + c["c_replycl"]
    w_peer = c["c_rel"] + c["c_repl"]
    one, zero = dt(1), dt(0)
    relay_work = c["c_fanout"] + (sizes_d - one) * w_peer + c["c_agg"]
    w_fol = dt((G * (p["costs"]["c_fanout"] + p["costs"]["c_agg"])
                + 2.0 * float((sizes - 1).sum())
                * (p["costs"]["c_rel"] + p["costs"]["c_repl"])) / F)

    kcl = np.asarray([k for k, _ in cells])
    K = int(kcl.max())
    kidx = np.arange(K)
    ready = np.where(kidx[None, :] < kcl[:, None],
                     dt(CLIENT_START) + dt(CLIENT_STAGGER) * kidx.astype(dt),
                     dt(np.inf)).astype(dt)
    cpuF = np.zeros((C, F + 1), dt)       # one spare slot: "no follower"
    loadF = np.zeros((C, F + 1), dt)
    cpuL = np.zeros(C, dt)
    loadL = np.zeros(C, dt)
    dt_ewma = np.ones(C, dt)
    t_prev = np.zeros(C, dt)
    keys = np.stack([np.asarray(jax.random.PRNGKey(kk)) for _, kk in cells])
    rows = np.arange(C)[:, None]
    rec = []
    step = 0
    max_steps = max_steps or int(
        K * float(stop) / (2 * p["base"]) / B) + 1
    while (ready < stop).any() and step < max_steps:
        if step % BLOCK == 0:
            e_blk, u_blk = draws(keys, step, BLOCK, B, pad)
        e = e_blk[:, step % BLOCK].astype(dt) * jit          # (C, B, W)
        u = u_blk[:, step % BLOCK, :, :G]                     # (C, B, G) f32
        step += 1
        e_cl0, e_cl1 = e[..., 0], e[..., 1]
        e_Lr = e[..., 2:2 + G]
        e_rL = e[..., 2 + Gp:2 + Gp + G]
        fslot = np.minimum(slot, F - 1)
        e_rp = np.take(e[..., 2 + 2 * Gp:2 + 2 * Gp + F], fslot,
                       axis=-1)                               # (C, B, G, S)
        e_pr = np.take(e[..., 2 + 2 * Gp + Fp:2 + 2 * Gp + Fp + F], fslot,
                       axis=-1)

        # pop the B earliest-ready clients
        order = argsort(ready)[:, :B]
        t0 = np.take_along_axis(ready, order, axis=1)         # (C, B)
        active = t0 < stop
        any_active = active[:, 0]

        # leader ingress: one FIFO server, constant work T_l per request
        aL = t0 + base + e_cl0
        start = np.empty_like(aL)
        free = cpuL
        for b in range(B):
            start[:, b] = np.maximum(aL[:, b], free)
            free = start[:, b] + T_l
        cpuL_next = np.maximum(
            cpuL, np.where(active, start + T_l, dt(-np.inf)).max(axis=1))
        W_L = start - aL
        L1 = start + c["c_req"]
        fan_done = L1[..., None] + np.arange(1, G + 1).astype(dt) \
            * c["c_fanout"]                                    # (C, B, G)
        cpuL2 = L1 + ng * c["c_fanout"]

        # relay of each group: rotating = a uniform member, static = first
        if p["static"]:
            j_rel = np.zeros((C, B, G), np.int64)
        else:
            j_rel = np.floor(u * sizes.astype(np.float32)).astype(np.int64)
        j_rel = np.clip(j_rel, 0, sizes - 1)
        rel_slot = gstart + j_rel                              # (C, B, G)

        # pacing-interval EWMA -> follower utilisation and M/D/1 floor
        n_act = np.maximum(active.sum(axis=1), 1).astype(dt)
        last_L1 = np.where(active, L1, dt(-np.inf)).max(axis=1)
        dt_ewma = np.where(any_active,
                           dt(0.95) * dt_ewma
                           + dt(0.05) * (last_L1 - t_prev) / n_act,
                           dt_ewma)
        t_prev = np.where(any_active, last_L1, t_prev)
        rho = np.minimum(np.maximum(w_fol / np.maximum(dt_ewma, dt(1e-9)),
                                    zero), dt(0.95))
        md1 = rho * w_peer / (dt(2) * (one - rho))
        vco = (rho - one)[:, None]                             # (C, 1)
        md = md1[:, None]

        # relay receives the fan-out, then re-broadcasts to its peers
        L1g = L1[..., None]
        arr_rel = fan_done + base + e_Lr
        B_r = cpuF[rows[..., None], rel_slot] - L1g            # (C, B, G)
        W_r = np.maximum(B_r + vco[..., None] * (arr_rel - L1g), zero) \
            + md[..., None]
        h = arr_rel + W_r + c["c_fanout"]
        is_rel = posn == j_rel[..., None]                      # (C, B, G, S)
        peer = member & ~is_rel
        send_i = posn - (posn > j_rel[..., None])
        send_done = h[..., None] + (send_i + 1).astype(dt) * c["c_rel"]
        arr_p = send_done + base + e_rp
        L1s = L1[..., None, None]
        cpu_p = cpuF[rows[..., None, None], np.broadcast_to(slot, peer.shape)]
        W_p = np.maximum(cpu_p - L1s + vco[..., None, None] * (arr_p - L1s),
                         zero) + md[..., None, None]
        arr_back = np.where(peer, arr_p + W_p + c["c_rel"] + c["c_repl"]
                            + base + e_pr, dt(np.inf))

        # relay FIFO over its peers' replies: flush once kcap + 1 have
        # been processed (the relay's own vote makes up the threshold)
        relay_free0 = h + (sizes_d - one) * c["c_rel"]
        replies = sort(arr_back)
        done = relay_free0.copy()
        done_k = relay_free0.copy()
        for j in range(S):
            a = replies[..., j]
            wait = np.maximum(B_r + vco[..., None] * (a - L1g), zero) \
                + md[..., None]
            done = np.maximum(done, a + wait) + c["c_repl"]
            done_k = np.where(kcap == j, done, done_k)
        flush = np.where(thresh >= 2, done_k, relay_free0)
        agg_in = flush + c["c_agg"] + base + e_rL              # (C, B, G)

        # leader FIFO over the aggregates: commit at the one that makes
        # a majority together with the leader's own vote
        ordg = argsort(agg_in)
        a_s = np.take_along_axis(agg_in, ordg, axis=-1)
        acks = np.cumsum(thresh[ordg], axis=-1)
        doneL = cpuL2.copy()
        commit = np.full((C, B), np.inf, dt)
        for k in range(G):
            doneL = np.maximum(doneL, a_s[..., k] + W_L) + c["c_agg"]
            first = (1 + acks[..., k] >= p["majority"]) & ~np.isfinite(commit)
            commit = np.where(first, doneL, commit)
        t_fin = commit + c["c_replycl"] + base + e_cl1

        # follower backlogs grow by this burst's work, from the first
        # active request's pacing point
        act = active[..., None, None]
        add = (scatter_add(F + 1, np.where(act & peer, slot, F), w_peer, dt)
               + scatter_add(F + 1, np.where(active[..., None], rel_slot, F),
                             relay_work, dt))
        anch = np.maximum(cpuF, np.where(any_active, L1[:, 0], zero)[:, None])
        cpuF = np.where(any_active[:, None], anch + add, cpuF)
        cpuF[:, F] = zero
        cpuL = np.where(any_active, cpuL_next, cpuL)
        np.put_along_axis(ready, order,
                          np.where(active, t_fin, dt(np.inf)), axis=1)

        # message loads of requests committed inside the window
        in_win = active & (commit >= warmup) & (commit <= stop + dt(DRAIN_S))
        iw = in_win[..., None, None]
        loadF = (loadF + scatter_add(F + 1, np.where(iw & peer, slot, F),
                                     dt(2), dt)
                 + scatter_add(F + 1, np.where(in_win[..., None], rel_slot, F),
                               dt(2) * sizes_d, dt))
        loadF[:, F] = zero
        loadL = loadL + np.where(in_win, dt(2) * ng + dt(2), zero).sum(
            axis=1, dtype=dt)
        check_dtype(dt, ready, cpuF, loadF, cpuL, loadL, dt_ewma, t_prev,
                    t_fin, commit)
        rec.append((t0, t_fin, commit, active))
    return summarize(rec, loadF[:, :F].sum(axis=1), loadL, F, warmup, stop,
                     duration, step)


def check_dtype(dt, *arrays) -> None:
    """Every state array stays in the run's dtype (a control that slipped
    into a wider dtype would not be the control)."""
    for a in arrays:
        if a.dtype != np.dtype(dt):
            raise TypeError(f"reference state in {a.dtype}, not "
                            f"{np.dtype(dt)}")


def argsort(x):
    """Stable ascending order along the last axis.  Every dtype used here
    is exactly representable in float64, whose sort orders +-inf and NaN
    safely (a low-precision dtype's own sort may not)."""
    return np.argsort(x.astype(np.float64), axis=-1, kind="stable")


def sort(x):
    return np.take_along_axis(x, argsort(x), axis=-1)


def scatter_add(n: int, idx, vals, dt):
    """out[c, s] = sum of ``vals`` (broadcast to ``idx``) over the entries
    of row c whose ``idx`` is s, summed in ``dt``."""
    C = idx.shape[0]
    vals = np.broadcast_to(np.asarray(vals, dt), idx.shape).reshape(C, -1)
    hit = idx.reshape(C, -1)[..., None] == np.arange(n)
    return np.where(hit, vals[..., None], dt(0)).sum(axis=1, dtype=dt)


def summarize(rec, loadF, loadL, followers, warmup, stop, duration, steps):
    """Client-side results over [warmup, stop]; commits up to stop + drain."""
    t0, t_fin, commit, active = (np.concatenate([r[i] for r in rec], axis=1)
                                 for i in range(4))
    out = {k: [] for k in ("count", "committed", "median_s", "p99_s",
                           "m_leader", "m_follower")}
    for ci in range(t0.shape[0]):
        a = active[ci]
        in_lat = a & (t_fin[ci] >= warmup) & (t_fin[ci] <= stop)
        in_com = a & (commit[ci] >= warmup) & (commit[ci] <= stop + DRAIN_S)
        lat = (t_fin[ci] - t0[ci])[in_lat].astype(np.float64)
        cnt, com = int(in_lat.sum()), int(in_com.sum())
        comf = max(com, 1)
        out["count"].append(cnt)
        out["committed"].append(com)
        med, p99 = np.percentile(lat, [50, 99]) if cnt else (math.nan,) * 2
        out["median_s"].append(float(med))
        out["p99_s"].append(float(p99))
        out["m_leader"].append(float(loadL[ci]) / comf)
        out["m_follower"].append(float(loadF[ci]) / (followers * comf))
    res = {k: np.asarray(v) for k, v in out.items()}
    res["throughput"] = res["count"] / float(duration)
    res["steps"] = steps
    res["dtype"] = np.dtype(t_fin.dtype).name
    return res
