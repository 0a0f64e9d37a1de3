"""Plain per-round numpy reference of the simulator's ``private`` and
``ata`` policies on a sectored geometry, under the ``ideal``
interconnect.

Written from the model's description, not from the program's code, and
importing nothing of it. ``S = sectors_per_line``; a line's tag covers
``S`` sectors and each way keeps the mask of the sectors it holds. A
request is a line ``addr``, a sector mask ``sect`` in ``1 .. 2**S - 1``
and a write bit. One round: every core issues ``m`` requests;

1. L1: a local hit is a tag hit in the core's own array whose way holds
   every requested sector; a tag hit lacking some of them is an L1
   sector miss. ``ata`` compares the request against every L1 tag
   array of its cluster at once: a read that is not a local hit, where
   the first peer (in slot order) whose way holds the line with every
   requested sector holds it clean, is a remote hit; it queues at that
   peer's data port behind earlier remote hits to the same peer.
   Writes are never served remotely. A local hit refreshes the line's
   LRU time and, for a write, marks it dirty. A request needs ``need``
   sectors from outside: its requested sectors its own way lacks where
   its own L1 had the tag, all of them otherwise.
2. L2: every request not served in the L1 complex goes to its L2
   partition (``addr % parts``, set ``addr // parts % sets``) for
   ``need``, queues behind earlier L2 requests to the same partition,
   and pays DRAM where its way lacks a needed sector (an L2 sector miss
   where the L2 had the tag); the sectors it lacks come from DRAM. L2
   tag hits refresh the LRU time and OR the fetched sectors into their
   way; then the L2 tag misses, in request order, go to the LRU way
   (chosen before this round's L2 updates): one that holds the line at
   that moment ORs the needed sectors in, any other is replaced by the
   line holding ``need``.
3. L1 fill: every L2 return and every remote hit fills ``need`` into
   the requester's own L1, at the way holding the line's tag, else the
   LRU way. Fills apply in request order (core, then slot): a way that
   holds the line at that moment takes the OR of the sectors and the
   dirty bit, any other is replaced (sectors ``need``, dirty = the
   request's write bit), and a dirty victim costs a write-back of its
   valid sectors.
4. Flits: a transfer of ``k`` sectors costs ``k * flits_per_line / S``
   flits: remote data and L2 returns carry ``need``, write-backs the
   victim's valid sectors.
5. Timing: a request's latency is its L1 time if served in the L1
   complex, else tag check + L2 time. A core's round costs the largest
   of its issue pace, its port/partition occupancy and its mean
   latency over the hiding factor.

Caches start empty. Counters are exact integers; timing accumulates in
``dtype`` (float64 for the reference, a lower precision for its
control).
"""
from __future__ import annotations

import numpy as np

TAG_CHECK = 8
_INT_MIN = np.iinfo(np.int64).min


def _rank(key, mask):
    """Per request: earlier masked requests with the same key, and the
    masked group size. key, mask: (P, R), key >= 0."""
    P, R = key.shape
    # sort by key (unmasked requests as key -1), request order kept
    order = np.argsort(np.where(mask, key, -1), axis=1, kind="stable")
    k = np.take_along_axis(np.where(mask, key, -1), order, 1)
    pos = np.broadcast_to(np.arange(R), (P, R))
    new = np.ones((P, R), bool)
    new[:, 1:] = k[:, 1:] != k[:, :-1]
    start = np.maximum.accumulate(np.where(new, pos, 0), axis=1)
    group = np.cumsum(new, axis=1) - 1
    count = np.zeros((P, R), np.int64)
    np.add.at(count, (np.arange(P)[:, None], group), 1)
    rank, size = np.empty((P, R), np.int64), np.empty((P, R), np.int64)
    np.put_along_axis(rank, order, pos - start, 1)
    np.put_along_axis(size, order, np.take_along_axis(count, group, 1), 1)
    return np.where(mask, rank, 0), np.where(mask, size, 0)


def _lru_way(valid, last):
    """First invalid way, else the least recently used (first on ties)."""
    return np.argmin(np.where(valid, last, _INT_MIN), axis=-1)


def _in_request_order(entry, mask):
    """The masked requests as (point, request) index arrays: first every
    request alone on its target entry, at once (their order cannot
    matter), then one at a time, in request order, those that share a
    target. entry: (P, R) target within a point, below 2**40."""
    P, R = entry.shape
    flat = np.nonzero(mask.ravel())[0]
    key = (entry + (np.arange(P)[:, None] << 40)).ravel()[flat]
    _, inv, counts = np.unique(key, return_inverse=True, return_counts=True)
    alone = flat[counts[inv] == 1]
    yield np.unravel_index(alone, (P, R))
    for i in flat[counts[inv] > 1]:
        yield np.unravel_index(np.array([i]), (P, R))


def simulate(geom: dict, arch: str, addr, is_write, sect, insn,
             dtype=np.float64):
    """Run stacked same-shape traces through one policy.

    addr, is_write, sect: (P, T, C, m); insn: (P,) instructions per
    request. Returns one dict of raw totals per point.
    """
    if arch not in ("private", "ata"):
        raise ValueError(f"reference covers private and ata, not {arch!r}")
    P, T, C, m = addr.shape
    R = C * m
    G = geom["cluster_size"]
    S = geom["sectors_per_line"]
    S1, W1 = geom["l1_sets"], geom["l1_ways"]
    N2, S2, W2 = geom["l2_parts"], geom["l2_sets"], geom["l2_ways"]
    f = lambda x: np.asarray(x, dtype)

    tags1 = np.zeros((P, C, S1, W1), np.int32)
    valid1 = np.zeros((P, C, S1, W1), bool)
    dirty1 = np.zeros((P, C, S1, W1), bool)
    last1 = np.full((P, C, S1, W1), -1, np.int64)
    have1 = np.zeros((P, C, S1, W1), np.int64)
    tags2 = np.zeros((P, N2, S2, W2), np.int64)
    valid2 = np.zeros((P, N2, S2, W2), bool)
    last2 = np.full((P, N2, S2, W2), -1, np.int64)
    have2 = np.zeros((P, N2, S2, W2), np.int64)

    p_ = np.arange(P)[:, None]
    core = np.repeat(np.arange(C), m)                    # (R,)
    cluster = core // G
    slot = core % G
    peers = cluster[:, None] * G + np.arange(G)[None, :]  # (R, G)
    is_self = np.arange(G)[None, :] == slot[:, None]      # (R, G)
    core_b = np.broadcast_to(core, (P, R))

    tot = {k: np.zeros(P, np.int64) for k in
           ("local", "remote", "l2", "dram", "lat_n", "lat_sum",
            "flit_sectors", "remote_sectors", "l1_sector_misses",
            "l2_sector_misses", "dram_sectors")}
    cycles = np.zeros((P, C), dtype)
    pace = f(m * f(insn) / f(geom["issue_rate"]))[:, None]   # (P, 1)

    for t in range(T):
        a = addr[:, t].reshape(P, R).astype(np.int64)
        w = is_write[:, t].reshape(P, R)
        q = sect[:, t].reshape(P, R).astype(np.int64)
        s = a % S1

        # ---- L1 -----------------------------------------------------
        if arch == "private":
            match = (tags1[p_, core_b, s] == a[..., None]) \
                & valid1[p_, core_b, s]
            tag_hit = match.any(-1)
            way = np.argmax(match, -1)
            lack = q & ~have1[p_, core_b, s, way]
            local = tag_hit & (lack == 0)
            remote = np.zeros((P, R), bool)
            occ = np.zeros((P, R), np.int64)
            l1_time = np.where(local, geom["lat_l1"], TAG_CHECK)
        else:
            # every peer's (set) row, as one index into (P*C*S1, W) rows
            rows = (p_[..., None] * C + peers) * S1 + s[:, :, None]
            row = lambda x: x.reshape(-1, W1)[rows]          # (P, R, G, W)
            match = (row(tags1) == a[..., None, None]) & row(valid1)
            hits = match.any(-1)
            ways = np.argmax(match, -1)                      # (P, R, G)
            # a line sits in at most one way of a set
            at = lambda x: x.reshape(-1, W1)[rows, ways]
            dirt = hits & at(dirty1)
            lacks = q[..., None] & ~at(have1)
            full = hits & (lacks == 0)
            tag_hit = (hits & is_self).any(-1)
            way = np.take_along_axis(ways, slot[None, :, None], -1)[..., 0]
            lack = np.take_along_axis(lacks, slot[None, :, None], -1)[..., 0]
            local = (full & is_self).any(-1)
            rfull = full & ~is_self
            src_slot = np.argmax(rfull, -1)
            src = cluster[None, :] * G + src_slot
            src_dirty = np.take_along_axis(dirt, src_slot[..., None],
                                           -1)[..., 0]
            remote = ~w & ~local & rfull.any(-1) & ~src_dirty
            prank, psize = _rank(src, remote)
            occ = np.where(remote, psize * geom["svc_port"], 0)
            l1_time = np.where(
                local, geom["lat_l1"],
                np.where(remote, geom["lat_l1"] + geom["lat_xbar"]
                         + prank * geom["svc_port"], TAG_CHECK))
        need = np.where(tag_hit, lack, q)
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
        tag_hit2 = m2.any(-1)
        way2 = np.where(tag_hit2, np.argmax(m2, -1),
                        _lru_way(valid2[p_, part, set2],
                                 last2[p_, part, set2]))
        fetch = np.where(tag_hit2, need & ~have2[p_, part, set2, way2], need)
        hit2 = fetch == 0
        rank2, size2 = _rank(part, to_l2)
        l2_time = geom["lat_l2"] + rank2 * geom["svc_l2"] \
            + np.where(hit2, 0, geom["lat_dram"])
        occ = np.maximum(occ, np.where(to_l2, size2 * geom["svc_l2"], 0))
        # tag hits: LRU refresh, fetched sectors ORed in (order-free)
        upd = to_l2 & tag_hit2
        i2 = (np.nonzero(upd)[0], part[upd], set2[upd], way2[upd])
        last2[i2] = t
        np.bitwise_or.at(have2, i2, fetch[upd])
        # tag misses, in request order
        for pk, rk in _in_request_order((part * S2 + set2) * W2 + way2,
                                        to_l2 & ~tag_hit2):
            i2 = (pk, part[pk, rk], set2[pk, rk], way2[pk, rk])
            line, n = a[pk, rk], need[pk, rk]
            held = valid2[i2] & (tags2[i2] == line)
            have2[i2] = np.where(held, have2[i2] | n, n)
            tags2[i2] = line
            valid2[i2] = True
            last2[i2] = t

        # ---- L1 fill --------------------------------------------------
        fill = to_l2 | remote
        own_t, own_v = tags1[p_, core_b, s], valid1[p_, core_b, s]
        fm = (own_t == a[..., None]) & own_v
        fway = np.where(fm.any(-1), np.argmax(fm, -1),
                        _lru_way(own_v, last1[p_, core_b, s]))
        for pk, rk in _in_request_order((core_b * S1 + s) * W1 + fway, fill):
            i1 = (pk, core[rk], s[pk, rk], fway[pk, rk])
            line, n, wk = a[pk, rk], need[pk, rk], w[pk, rk]
            held = valid1[i1] & (tags1[i1] == line)
            evict = ~held & valid1[i1] & dirty1[i1]
            np.add.at(tot["flit_sectors"], pk, np.where(
                evict, np.bitwise_count(have1[i1]).astype(np.int64), 0))
            have1[i1] = np.where(held, have1[i1] | n, n)
            dirty1[i1] = np.where(held, dirty1[i1] | wk, wk)
            tags1[i1] = line
            valid1[i1] = True
            last1[i1] = t

        # ---- totals and timing -----------------------------------------
        need_k = np.bitwise_count(need).astype(np.int64)
        tot["local"] += local.sum(-1)
        tot["remote"] += remote.sum(-1)
        tot["l2"] += to_l2.sum(-1)
        tot["dram"] += (to_l2 & ~hit2).sum(-1)
        tot["remote_sectors"] += np.where(remote, need_k, 0).sum(-1)
        tot["flit_sectors"] += np.where(remote | to_l2, need_k, 0).sum(-1)
        tot["l1_sector_misses"] += (tag_hit & (lack != 0)).sum(-1)
        tot["l2_sector_misses"] += (to_l2 & tag_hit2 & ~hit2).sum(-1)
        tot["dram_sectors"] += np.where(
            to_l2, np.bitwise_count(fetch).astype(np.int64), 0).sum(-1)
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

    per_sector = geom["flits_per_line"] / S
    out = []
    for p in range(P):
        d = {k: int(v[p]) for k, v in tot.items()}
        d["noc_flits"] = d.pop("flit_sectors") * per_sector
        d["injected"] = d.pop("remote_sectors") * per_sector
        d["requests"] = T * R
        d["instructions"] = T * C * m * float(insn[p])
        d["cycles_per_core"] = cycles[p].astype(np.float64)
        out.append(d)
    return out
