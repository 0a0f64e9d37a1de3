"""Plain per-round numpy reference of the simulator's ``private`` and
``ata`` policies under the ``ideal`` interconnect.

Written from the model's description, not from the program's code, and
importing nothing of it. One round: every core issues ``m`` requests;

1. L1: ``private`` looks up the core's own L1; ``ata`` compares the
   request against all L1 tag arrays of its cluster at once. A hit in
   the core's own array is local. A read that misses locally but hits
   a clean line in a peer's array (the first such peer in slot order)
   is a remote hit: it queues at that peer's data port behind earlier
   remote hits to the same peer. Writes are never served remotely. A
   local hit refreshes the line's LRU time and, for a write, marks it
   dirty.
2. L2: every request not served by the L1 complex goes to its L2
   partition (``addr % parts``, set ``addr // parts % sets``), queues
   behind earlier L2 requests to the same partition, and pays DRAM on
   a miss; a miss installs the line in the LRU way.
3. L1 fill: every L2 return and every remote hit installs the line in
   the requester's own L1 (LRU way, dirty = the request's write bit);
   a dirty victim costs a write-back.
4. Timing: a request's latency is its L1 time if served in the L1
   complex, else tag check + L2 time. A core's round costs the largest
   of its issue pace, its port/partition occupancy and its mean
   latency over the hiding factor.

Caches start empty. Updates that land on the same way in one round
keep the later request's line (request order: core, then slot).
Counters are exact integers; timing accumulates in ``dtype``
(float64 for the reference, a lower precision for its control).
"""
from __future__ import annotations

import numpy as np

TAG_CHECK = 8
_INT_MIN = np.iinfo(np.int64).min


def _rank(key, mask):
    """Per request: earlier masked requests with the same key, and the
    masked group size. key, mask: (P, R)."""
    R = key.shape[1]
    same = (key[:, :, None] == key[:, None, :]) & mask[:, None, :]
    earlier = np.tril(np.ones((R, R), bool), -1)
    rank = (same & earlier).sum(-1)
    size = same.sum(-1)
    return np.where(mask, rank, 0), np.where(mask, size, 0)


def _lru_way(valid, last):
    """First invalid way, else the least recently used (first on ties)."""
    return np.argmin(np.where(valid, last, _INT_MIN), axis=-1)


def _last_wins(flat, mask):
    """Mask of the masked requests whose write survives: the last one
    (in request order) of every group that targets the same entry.
    flat: (P, R) entry index within a point, below 2**40."""
    P, R = flat.shape
    keep = np.zeros(P * R, bool)
    idx = np.nonzero(mask.ravel())[0]
    if idx.size:
        key = (flat + (np.arange(P)[:, None] << 40)).ravel()[idx]
        _, first = np.unique(key[::-1], return_index=True)
        keep[idx[idx.size - 1 - first]] = True
    return keep.reshape(P, R)


def simulate(geom: dict, arch: str, addr, is_write, insn,
             dtype=np.float64):
    """Run stacked same-shape traces through one policy.

    addr, is_write: (P, T, C, m); insn: (P,) instructions per request.
    Returns one dict of raw totals per point.
    """
    if arch not in ("private", "ata"):
        raise ValueError(f"reference covers private and ata, not {arch!r}")
    P, T, C, m = addr.shape
    R = C * m
    G = geom["cluster_size"]
    S1, W1 = geom["l1_sets"], geom["l1_ways"]
    N2, S2, W2 = geom["l2_parts"], geom["l2_sets"], geom["l2_ways"]
    f = lambda x: np.asarray(x, dtype)

    tags1 = np.zeros((P, C, S1, W1), np.int64)
    valid1 = np.zeros((P, C, S1, W1), bool)
    dirty1 = np.zeros((P, C, S1, W1), bool)
    last1 = np.full((P, C, S1, W1), -1, np.int64)
    tags2 = np.zeros((P, N2, S2, W2), np.int64)
    valid2 = np.zeros((P, N2, S2, W2), bool)
    last2 = np.full((P, N2, S2, W2), -1, np.int64)

    p_ = np.arange(P)[:, None]
    core = np.repeat(np.arange(C), m)                    # (R,)
    cluster = core // G
    slot = core % G
    peers = cluster[:, None] * G + np.arange(G)[None, :]  # (R, G)
    is_self = np.arange(G)[None, :] == slot[:, None]      # (R, G)
    core_b = np.broadcast_to(core, (P, R))

    tot = {k: np.zeros(P, np.int64) for k in
           ("local", "remote", "l2", "dram", "noc_flits", "lat_n",
            "lat_sum", "injected")}
    cycles = np.zeros((P, C), dtype)
    pace = f(m * f(insn) / f(geom["issue_rate"]))[:, None]   # (P, 1)

    for t in range(T):
        a = addr[:, t].reshape(P, R).astype(np.int64)
        w = is_write[:, t].reshape(P, R)
        s = a % S1

        # ---- L1 -----------------------------------------------------
        if arch == "private":
            match = (tags1[p_, core_b, s] == a[..., None]) \
                & valid1[p_, core_b, s]
            local = match.any(-1)
            way = np.argmax(match, -1)
            remote = np.zeros((P, R), bool)
            occ = np.zeros((P, R), np.int64)
            l1_time = np.where(local, geom["lat_l1"], TAG_CHECK)
        else:
            pe = np.broadcast_to(peers, (P, R, G))
            se = s[:, :, None]
            match = (tags1[p_[..., None], pe, se] == a[..., None, None]) \
                & valid1[p_[..., None], pe, se]              # (P, R, G, W)
            hits = match.any(-1)
            ways = np.argmax(match, -1)
            dirt = (match & dirty1[p_[..., None], pe, se]).any(-1)
            local = (hits & is_self).any(-1)
            way = np.take_along_axis(ways, slot[None, :, None], -1)[..., 0]
            rhit = hits & ~is_self
            src_slot = np.argmax(rhit, -1)
            src = cluster[None, :] * G + src_slot
            src_dirty = np.take_along_axis(dirt, src_slot[..., None], -1)[..., 0]
            remote = ~w & ~local & rhit.any(-1) & ~src_dirty
            prank, psize = _rank(src, remote)
            occ = np.where(remote, psize * geom["svc_port"], 0)
            l1_time = np.where(
                local, geom["lat_l1"],
                np.where(remote, geom["lat_l1"] + geom["lat_xbar"]
                         + prank * geom["svc_port"], TAG_CHECK))
        served = local | remote
        # local hit: refresh LRU time, a write marks the line dirty
        pl, rl = np.nonzero(local)
        c_, s_, w_ = core[rl], s[pl, rl], way[pl, rl]
        last1[pl, c_, s_, w_] = t
        wr = w[pl, rl]
        dirty1[pl[wr], c_[wr], s_[wr], w_[wr]] = True

        # ---- L2 -----------------------------------------------------
        to_l2 = ~served
        part = a % N2
        set2 = (a // N2) % S2
        m2 = (tags2[p_, part, set2] == a[..., None]) & valid2[p_, part, set2]
        hit2 = m2.any(-1)
        way2 = np.where(hit2, np.argmax(m2, -1),
                        _lru_way(valid2[p_, part, set2],
                                 last2[p_, part, set2]))
        rank2, size2 = _rank(part, to_l2)
        l2_time = geom["lat_l2"] + rank2 * geom["svc_l2"] \
            + np.where(hit2, 0, geom["lat_dram"])
        occ = np.maximum(occ, np.where(to_l2, size2 * geom["svc_l2"], 0))
        upd = to_l2 & hit2
        last2[np.nonzero(upd)[0], part[upd], set2[upd], way2[upd]] = t
        ins = to_l2 & ~hit2
        keep = _last_wins((part * S2 + set2) * W2 + way2, ins)
        pk = np.nonzero(keep)[0]
        i2 = (pk, part[keep], set2[keep], way2[keep])
        tags2[i2] = a[keep]
        valid2[i2] = True
        last2[i2] = t

        # ---- L1 fill --------------------------------------------------
        fill = to_l2 | remote
        own_t, own_v = tags1[p_, core_b, s], valid1[p_, core_b, s]
        fm = (own_t == a[..., None]) & own_v
        fway = np.where(fm.any(-1), np.argmax(fm, -1),
                        _lru_way(own_v, last1[p_, core_b, s]))
        wb = fill & valid1[p_, core_b, s, fway] & dirty1[p_, core_b, s, fway]
        keep = _last_wins((core_b * S1 + s) * W1 + fway, fill)
        pk, rk = np.nonzero(keep)
        i1 = (pk, core[rk], s[keep], fway[keep])
        tags1[i1] = a[keep]
        valid1[i1] = True
        last1[i1] = t
        dirty1[i1] = w[keep]

        # ---- totals and timing -----------------------------------------
        fl = geom["flits_per_line"]
        tot["local"] += local.sum(-1)
        tot["remote"] += remote.sum(-1)
        tot["l2"] += to_l2.sum(-1)
        tot["dram"] += (to_l2 & ~hit2).sum(-1)
        tot["noc_flits"] += (remote.sum(-1) + to_l2.sum(-1)
                             + wb.sum(-1)) * fl
        tot["injected"] += remote.sum(-1) * fl
        latency = np.where(served, l1_time, TAG_CHECK + l2_time)
        core_lat = f(latency.reshape(P, C, m).sum(-1)) / f(m)
        core_occ = f(occ.reshape(P, C, m).max(-1))
        cost = np.maximum(np.maximum(pace, core_occ),
                          core_lat / f(geom["hide"]))
        cycles = (cycles + cost).astype(dtype)
        all_served = served.reshape(P, C, m).all(-1)
        tot["lat_n"] += all_served.sum(-1)
        tot["lat_sum"] += np.where(
            all_served, l1_time.reshape(P, C, m).max(-1), 0).sum(-1)

    out = []
    for p in range(P):
        d = {k: int(v[p]) for k, v in tot.items()}
        d["requests"] = T * R
        d["instructions"] = T * C * m * float(insn[p])
        d["cycles_per_core"] = cycles[p].astype(np.float64)
        out.append(d)
    return out
