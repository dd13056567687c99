"""LDBC-SNB-shaped temporal property graph, generated in bulk from a seed.

The same generative model as the system's ``graphdata/ldbc.py`` (persons,
posts, comments and forums; follows, likes, created, hasMember,
containerOf and replyOf; correlated properties with lifespans on a
day-granular three-year horizon snapped to a 16-bucket grid; the Facebook
lognormal follows degree), written with whole-array draws so that a
100k-person graph takes seconds rather than minutes.  Two departures keep
every array shape a function of the configuration alone, so that every
seed drives the same compiled programs:

* edge counts are fixed by the per-person ratios: the follows degrees are
  trimmed or topped up to ``round(avg_follows * n)``, memberships are drawn
  per membership rather than per person, and a follows self-loop is
  redrawn rather than dropped;
* multi-valued properties (tags, interests) are capped at a stated number
  of values per entity, and every property column has that many slots.

The output is plain numpy (``RawGraph``): the benchmark hands a copy to
the system under test and the original to the reference.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import numpy as np

T_HORIZON = 1096
VTYPES = ("person", "post", "comment", "forum")
ETYPES = ("follows", "likes", "created", "hasMember", "containerOf", "replyOf")
#: property keys in id order; ``length`` is ordered (its value is its id)
KEYS = ("country", "gender", "hasInterest", "worksAt", "tag", "language",
        "length")
N_COUNTRIES = 15
N_COMPANIES = 40
N_TAGS = 64
GENDERS = ("m", "f")
LANGS = ("en", "hi", "zh", "es", "de")


@dataclasses.dataclass
class RawGraph:
    """Structure-of-arrays graph; vertex ids are type-major (persons,
    posts, comments, forums).  A property is ``(vals int32[V, S],
    life int32[V, S, 2])`` with -1 and an empty lifespan in unused slots."""
    v_type: np.ndarray
    v_life: np.ndarray
    e_src: np.ndarray
    e_dst: np.ndarray
    e_type: np.ndarray
    e_life: np.ndarray
    vprops: Dict[str, Tuple[np.ndarray, np.ndarray]]
    dynamic: bool
    lifespan: Tuple[int, int] = (0, T_HORIZON)

    @property
    def n_vertices(self) -> int:
        return int(self.v_type.shape[0])

    @property
    def n_edges(self) -> int:
        return int(self.e_src.shape[0])


def _snap(t: np.ndarray, align: int) -> np.ndarray:
    """Floor to the ``ceil(T / align)`` grid (the system's bucket width)."""
    if not align:
        return t.astype(np.int64)
    step = -(-T_HORIZON // align)
    return (t // step) * step


def _after(rng, lo: np.ndarray, align: int) -> np.ndarray:
    """A grid start in ``[lo, T)``, at most ``T - 1``."""
    return np.minimum(_snap(rng.integers(lo, T_HORIZON), align), T_HORIZON - 1)


def _follow_sources(rng, n: int, avg: float, n_edges: int) -> np.ndarray:
    """Follows sources: Facebook-like lognormal out-degrees (as the system's
    generator draws them), trimmed or topped up to exactly ``n_edges``."""
    d = np.exp(rng.normal(np.log(avg) - 0.5, 1.0, size=n))
    d = np.clip(np.round(d * (avg / max(d.mean(), 1e-9))), 0, 20 * avg)
    src = np.repeat(np.arange(n), d.astype(np.int64))
    if src.size > n_edges:
        src = np.sort(rng.choice(src, size=n_edges, replace=False))
    elif src.size < n_edges:
        extra = rng.choice(n, size=n_edges - src.size, p=d / d.sum())
        src = np.sort(np.concatenate([src, extra]))
    return src


def _multi(rng, pool: np.ndarray, owners: np.ndarray, mean: float, cap: int,
           n_slots: int):
    """Per owner ``min(max(1, Poisson(mean)), cap)`` draws from ``pool``,
    deduplicated; returns (owner, value) pairs, owner-sorted."""
    k = np.minimum(np.maximum(1, rng.poisson(mean, size=owners.size)), cap)
    own = np.repeat(owners, k)
    val = rng.choice(pool, size=own.size)
    pair = np.unique(own.astype(np.int64) * n_slots + val)
    return pair // n_slots, pair % n_slots


def _column(n: int, slots: int, ids: np.ndarray, vals: np.ndarray,
            lo: np.ndarray, hi: np.ndarray):
    """Pivot (entity, value, lifespan) rows, entity-sorted, into slots."""
    out_v = np.full((n, slots), -1, np.int32)
    out_l = np.zeros((n, slots, 2), np.int32)
    ids = np.asarray(ids, np.int64)
    first = np.searchsorted(ids, ids, side="left")
    slot = np.arange(ids.size) - first
    assert slot.size == 0 or slot.max() < slots, "more values than slots"
    out_v[ids, slot] = vals
    out_l[ids, slot, 0] = lo
    out_l[ids, slot, 1] = hi
    return out_v, out_l


def generate(p: dict, seed: int) -> RawGraph:
    """The graph of configuration ``p`` (the config file's ``graph``)."""
    rng = np.random.default_rng(seed)
    align = int(p["align"])
    T = T_HORIZON
    N = int(p["n_persons"])
    P = int(p["posts_per_person"] * N)
    C = int(p["comments_per_person"] * N)
    F = int(p["forums_per_person"] * N)
    V = N + P + C + F
    o_post, o_com, o_for = N, N + P, N + P + C
    dyn = bool(p["dynamic"])
    cap_tags = int(p["max_tags_per_message"])
    cap_int = int(p["max_interests"])

    # ---------------------------------------------------------- persons
    p_start = _snap(rng.integers(0, int(T * 0.9), size=N), align)
    tag_pop = rng.zipf(1.6, size=4 * N) % N_TAGS
    persons = np.arange(N)
    gender = rng.integers(0, 2, size=N)
    if dyn:
        # country and worksAt change over time: 1-3 segments, a value used
        # at most once per person
        n_seg = rng.integers(1, 4, size=N)
        cuts = _snap(rng.integers(p_start[:, None], T, size=(N, 2)), align)
        cuts = np.where(np.arange(2)[None, :] < (n_seg - 1)[:, None], cuts,
                        p_start[:, None])
        b = np.sort(np.concatenate([p_start[:, None], cuts,
                                    np.full((N, 1), T)], axis=1), axis=1)
        seg_ok = b[:, :-1] < b[:, 1:]
        pid, k = np.nonzero(seg_ok)
        ctry = np.argsort(rng.random((N, N_COUNTRIES)), axis=1)[:, :3]
        comp = np.argsort(rng.random((N, N_COMPANIES)), axis=1)[:, :3]
        country = _column(V, 3, pid, ctry[pid, k], b[pid, k], b[pid, k + 1])
        works = _column(V, 3, pid, comp[pid, k], b[pid, k], b[pid, k + 1])
    else:
        country = _column(V, 1, persons, rng.integers(0, N_COUNTRIES, size=N),
                          p_start, np.full(N, T))
        works = _column(V, 1, persons, rng.integers(0, N_COMPANIES, size=N),
                        p_start, np.full(N, T))
    ip, it = _multi(rng, tag_pop, persons, p["interests_per_person"],
                    cap_int, N_TAGS)
    if dyn:
        i_lo = _after(rng, p_start[ip], align)
    else:
        i_lo = p_start[ip]
    interest = _column(V, cap_int, ip, it, i_lo, np.full(ip.size, T))

    # ---------------------------------------------------------- follows
    n_fol = int(round(p["avg_follows"] * N))
    f_src = _follow_sources(rng, N, p["avg_follows"], n_fol)
    f_dst = rng.integers(0, N, size=n_fol)
    loop = f_dst == f_src
    f_dst[loop] = (f_src[loop] + 1 + rng.integers(0, N - 1, size=loop.sum())) % N
    f_st = _after(rng, np.maximum(p_start[f_src], p_start[f_dst]), align)
    step = -(-T // align) if align else 1
    ends = rng.random(n_fol) < 0.35     # some follows end (unfollow)
    en = _snap(rng.integers(f_st + 1, T + 1), align)
    en = np.minimum(np.where(en <= f_st, f_st + step, en), T)
    f_en = np.where(ends, en, T)

    # ----------------------------------------------------------- forums
    fo_start = _snap(rng.integers(0, int(T * 0.9), size=F), align)
    forum_tag = rng.choice(tag_pop, size=F)
    n_mem = int(round(p["memberships_per_person"] * N))
    m_person = rng.integers(0, N, size=n_mem)
    m_forum = rng.integers(0, F, size=n_mem)
    m_st = _after(rng, np.maximum(p_start[m_person], fo_start[m_forum]), align)

    # ------------------------------------------------------------ posts
    po_creator = rng.integers(0, N, size=P)
    po_forum = rng.integers(0, F, size=P)
    po_start = _after(rng, np.maximum(p_start[po_creator],
                                      fo_start[po_forum]), align)
    po_lang = rng.integers(0, len(LANGS), size=P)
    po_len = rng.integers(1, 500, size=P)

    # --------------------------------------------------------- comments
    c_creator = rng.integers(0, N, size=C)
    c_parent = rng.integers(0, P, size=C)
    c_start = _after(rng, np.maximum(p_start[c_creator],
                                     po_start[c_parent]), align)
    c_len = rng.integers(1, 200, size=C)

    # ------------------------------------------------------------ likes
    n_likes = int(round(p["likes_per_person"] * N))
    l_person = rng.integers(0, N, size=n_likes)
    l_post = rng.integers(0, P, size=n_likes)
    l_st = _after(rng, np.maximum(p_start[l_person], po_start[l_post]), align)

    # ------------------------------------------------- message properties
    msgs = np.concatenate([np.arange(o_post, o_post + P),
                           np.arange(o_com, o_com + C)])
    msg_start = np.concatenate([po_start, c_start])
    tm, tv = _multi(rng, tag_pop, msgs, p["tags_per_message"], cap_tags,
                    N_TAGS)
    fids = np.arange(o_for, V)
    t_ids = np.concatenate([tm, fids])
    t_vals = np.concatenate([tv, forum_tag])
    t_lo = np.concatenate([msg_start[tm - o_post], fo_start])
    tag = _column(V, cap_tags, t_ids, t_vals, t_lo, np.full(t_ids.size, T))
    posts = np.arange(o_post, o_post + P)
    language = _column(V, 1, posts, po_lang, po_start, np.full(P, T))
    length = _column(V, 1, msgs, np.concatenate([po_len, c_len]), msg_start,
                     np.full(P + C, T))
    gender_col = _column(V, 1, persons, gender, p_start, np.full(N, T))

    # --------------------------------------------------------- assemble
    v_type = np.concatenate([np.full(N, 0), np.full(P, 1), np.full(C, 2),
                             np.full(F, 3)]).astype(np.int32)
    v_start = np.concatenate([p_start, po_start, c_start, fo_start])
    v_life = np.stack([v_start, np.full(V, T)], axis=1).astype(np.int32)
    et = {n: i for i, n in enumerate(ETYPES)}
    full = lambda n: np.full(n, T)  # noqa: E731
    parts = [  # (src, dst, type, start, end)
        (f_src, f_dst, et["follows"], f_st, f_en),
        (l_person, o_post + l_post, et["likes"], l_st, full(n_likes)),
        (po_creator, posts, et["created"], po_start, full(P)),
        (c_creator, np.arange(o_com, o_com + C), et["created"], c_start,
         full(C)),
        (o_for + m_forum, m_person, et["hasMember"], m_st, full(n_mem)),
        (o_for + po_forum, posts, et["containerOf"], po_start, full(P)),
        (np.arange(o_com, o_com + C), o_post + c_parent, et["replyOf"],
         c_start, full(C)),
    ]
    e_src = np.concatenate([s for s, _, _, _, _ in parts]).astype(np.int32)
    e_dst = np.concatenate([d for _, d, _, _, _ in parts]).astype(np.int32)
    e_type = np.concatenate([np.full(len(s), t) for s, _, t, _, _ in parts]
                            ).astype(np.int32)
    e_life = np.stack([np.concatenate([a for _, _, _, a, _ in parts]),
                       np.concatenate([b for _, _, _, _, b in parts])],
                      axis=1).astype(np.int32)
    vprops = dict(country=country, gender=gender_col, hasInterest=interest,
                  worksAt=works, tag=tag, language=language, length=length)
    return RawGraph(v_type, v_life, e_src, e_dst, e_type, e_life, vprops, dyn)


def freq_values(g: RawGraph, key: str, top_frac: float = 0.6) -> np.ndarray:
    """The most frequent values of a key (the parameter pools the system's
    workload generator draws from: its top 60% by frequency)."""
    vals = g.vprops[key][0].reshape(-1)
    vals = vals[vals >= 0]
    if vals.size == 0:
        return np.zeros(1, np.int64)
    uniq, cnts = np.unique(vals, return_counts=True)
    order = np.argsort(-cnts, kind="stable")
    keep = max(1, int(len(uniq) * top_frac))
    return uniq[order[:keep]].astype(np.int64)
