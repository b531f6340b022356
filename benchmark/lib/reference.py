"""The plain reference: what a match IS, and which matches a plain
matcher forms, by what the clients sent.

Imports nothing of the program and takes nothing the program made. It
reads only the queries the benchmark's recipes write and refuses any
other, so a recipe outside its grammar fails loudly instead of passing
unchecked:

    *                               accepts every ticket
    +properties.<field>:<word>      the ticket's string property == word
    +properties.<field>:<number>    the ticket's numeric property == number
    +properties.<field>:>=<number>  numeric property >= number
    +properties.<field>:<=<number>  numeric property <= number

A match is valid (the reference server's semantics, matchmaker_process.go
processDefault, `rev_precision` off) when its sessions are distinct, its
size lies inside every member's [min_count, max_count], and SOME
member's query (the active ticket that searched) accepts every other
member; with `rev_precision` on, every other member's query must accept
it back.

`replay` is the plain matcher: the reference server's interval loop
restated over whole arrays. At every tick the tickets acknowledged since
the last one search (a ticket whose min_count equals its max_count
searches once, any other for `max_intervals` ticks); each, oldest
first, lists its `k` best candidates still in the pool, by embedding
similarity in float32 where the tickets carry one and oldest first
among equals, and takes the first free ones until its match is full.
The candidate lists are computed in blocks of rows (jax.numpy, on
whatever backend the run has, after the program's state is freed).

With its keywords `replay` is also the CONTROLS, the shortcuts a later
change would be tempted by; each has to come out as not correct
(tests/test_reference.py, and on the chip scripts/control.py):

  precision="bfloat16"  numeric comparisons and embeddings rounded one
                        step below what the configuration states
  k=<fewer>             the candidate search cut short
  use_emb=False         the embedding ignored: candidates oldest first
"""

from __future__ import annotations

import math
import re

import numpy as np

_TERM = re.compile(
    r"^\+properties\.([A-Za-z_][A-Za-z0-9_]*):(>=|<=)?([^<>=\s]\S*)$"
)
_NUMBER = re.compile(r"^-?\d+(\.\d+)?$")


def parse(query: str) -> list[tuple[str, str, object]]:
    """[(field, op, value)] with op in {"str", "==", ">=", "<="}; the
    wildcard query is the empty list."""
    if query.strip() == "*":
        return []
    terms = []
    for word in query.split():
        m = _TERM.match(word)
        if m is None:
            raise ValueError(f"query outside the reference's grammar: {query}")
        field, op, value = m.groups()
        if op:
            if not _NUMBER.match(value):
                raise ValueError(f"bad number in query: {query}")
            terms.append((field, op, float(value)))
        elif _NUMBER.match(value):
            terms.append((field, "==", float(value)))
        else:
            terms.append((field, "str", value))
    if not terms:
        raise ValueError("empty query")
    return terms


def accepts(terms, strs: dict, nums: dict) -> bool:
    """Does a query accept a ticket with these properties (f64, exact)?"""
    for field, op, value in terms:
        if op == "str":
            if strs.get(field) != value:
                return False
            continue
        x = nums.get(field)
        if x is None:
            return False
        x = float(x)
        if (op == "==" and x != value) or (op == ">=" and not x >= value) or (
            op == "<=" and not x <= value
        ):
            return False
    return True


def match_fault(members: list[dict], rev: bool) -> str | None:
    """None when the match is valid, else what is wrong with it. A
    member is {"session", "query", "min_count", "max_count", "strs",
    "nums"}: the query and counts as the client SENT them, the
    properties as the match envelope carries them."""
    sessions = [m["session"] for m in members]
    if len(set(sessions)) != len(sessions):
        return "a session twice in one match"
    size = len(members)
    if size < 2:
        return "a match of one"
    for m in members:
        if not m["min_count"] <= size <= m["max_count"]:
            return f"size {size} outside [{m['min_count']}, {m['max_count']}]"
    terms = [parse(m["query"]) for m in members]

    def ok(i, j):
        return accepts(terms[i], members[j]["strs"], members[j]["nums"])

    for i in range(size):
        if all(
            ok(i, j) and (not rev or ok(j, i))
            for j in range(size) if j != i
        ):
            return None
    return "no member's query accepts the others"


# ---------------------------------------------------------- whole sets


def encode(specs: list[dict]) -> dict:
    """Column form of `specs` (each {"query", "min_count", "max_count",
    "strs", "nums"} and, for all or none, "emb"): per field the value
    every ticket carries and the constraint every ticket's query puts
    on it."""
    n = len(specs)
    parsed = [parse(s["query"]) for s in specs]
    sfields = sorted(
        {f for s in specs for f in s["strs"]}
        | {f for t in parsed for f, op, _ in t if op == "str"}
    )
    nfields = sorted(
        {f for s in specs for f in s["nums"]}
        | {f for t in parsed for f, op, _ in t if op != "str"}
    )
    words: dict[str, int] = {}

    def code(w: str) -> int:
        return words.setdefault(w, len(words))

    s_val = np.full((n, max(1, len(sfields))), -1, np.int32)
    s_req = np.full((n, max(1, len(sfields))), -1, np.int32)
    n_val = np.full((n, max(1, len(nfields))), np.nan, np.float32)
    n_lo = np.full((n, max(1, len(nfields))), -np.inf, np.float32)
    n_hi = np.full((n, max(1, len(nfields))), np.inf, np.float32)
    n_con = np.zeros((n, max(1, len(nfields))), bool)
    for i, (s, terms) in enumerate(zip(specs, parsed)):
        for f, w in s["strs"].items():
            s_val[i, sfields.index(f)] = code(w)
        for f, x in s["nums"].items():
            _exact32(x)
            n_val[i, nfields.index(f)] = x
        for f, op, v in terms:
            if op == "str":
                j = sfields.index(f)
                if s_req[i, j] >= 0 and s_req[i, j] != code(v):
                    s_req[i, j] = -2  # two different words: accepts none
                else:
                    s_req[i, j] = code(v)
                continue
            _exact32(v)
            j = nfields.index(f)
            n_con[i, j] = True
            if op in (">=", "=="):
                n_lo[i, j] = max(n_lo[i, j], v)
            if op in ("<=", "=="):
                n_hi[i, j] = min(n_hi[i, j], v)
    with_emb = [s.get("emb") is not None for s in specs]
    if any(with_emb) and not all(with_emb):
        raise ValueError("some tickets carry an embedding and some do not")
    emb = np.stack([np.asarray(s["emb"], np.float32) for s in specs]) \
        if n and all(with_emb) else np.zeros((n, 1), np.float32)
    return dict(
        s_val=s_val, s_req=s_req, n_val=n_val, n_lo=n_lo, n_hi=n_hi,
        n_con=n_con, emb=emb,
        min_c=np.array([s["min_count"] for s in specs], np.int32),
        max_c=np.array([s["max_count"] for s in specs], np.int32),
    )


def has_embeddings(specs: list[dict]) -> bool:
    return bool(specs) and specs[0].get("emb") is not None


def _exact32(x: float) -> None:
    if not math.isfinite(x) or float(np.float32(x)) != float(x):
        raise ValueError(f"{x} is not exact in float32: the block "
                         "reference compares in float32")


def _accept(rows, cols, dtype):
    """[R, C] bool: row ticket's query accepts column ticket."""
    ok = None
    for f in range(rows["s_req"].shape[1]):
        req = rows["s_req"][:, f][:, None]
        t = (req == -1) | (req == cols["s_val"][:, f][None, :])
        ok = t if ok is None else ok & t
    for f in range(rows["n_lo"].shape[1]):
        v = cols["n_val"][:, f].astype(dtype)[None, :]
        lo = rows["n_lo"][:, f].astype(dtype)[:, None]
        hi = rows["n_hi"][:, f].astype(dtype)[:, None]
        inside = (v >= lo) & (v <= hi)  # a missing value is NaN: False
        ok = ok & (~rows["n_con"][:, f][:, None] | inside)
    return ok


_BLOCK = 1024


def _pad_rows(a: np.ndarray, pad: int) -> np.ndarray:
    out = np.zeros((pad,) + a.shape[1:], a.dtype)
    out[:a.shape[0]] = a
    return out


class Candidates:
    """The candidate search over one encoded set: `top(rows, in_pool)`
    gives each row ticket's `k` best candidates among the tickets that
    `in_pool` marks, best first, -1 where there are fewer.

    A column is a candidate of a row when the row's query accepts it
    (and it the row, when `rev`), its count range lies inside the row's
    (matchmaker_process.go:65-85) and it is another ticket. Best is the
    highest embedding dot product (float32, `highest` precision) and,
    among equals, the lower index: callers index tickets oldest first."""

    def __init__(self, enc: dict, k: int, rev: bool,
                 precision: str = "float32", use_emb: bool = True):
        import jax
        import jax.numpy as jnp

        self.n = enc["s_val"].shape[0]
        self.k = k
        self.pad = -(-max(self.n, 1) // _BLOCK) * _BLOCK
        self.k_eff = min(k, self.pad)
        full = {key: _pad_rows(v, self.pad) for key, v in enc.items()}
        self._host = full
        self._cols = {key: jnp.asarray(v) for key, v in full.items()}
        dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[precision]
        col_idx = jnp.arange(self.pad, dtype=jnp.int32)
        neg = jnp.float32(-jnp.inf)
        k_eff = self.k_eff

        def one(rows, row_idx, cols, in_pool):
            ok = _accept(rows, cols, dtype)
            if rev:
                ok = ok & _accept(cols, rows, dtype).T
            ok = ok & (cols["min_c"][None, :] >= rows["min_c"][:, None])
            ok = ok & (cols["max_c"][None, :] <= rows["max_c"][:, None])
            ok = ok & (col_idx[None, :] != row_idx[:, None])
            ok = ok & in_pool[None, :]
            if use_emb:
                r, c = rows["emb"].astype(dtype), cols["emb"].astype(dtype)
                sim = jnp.dot(
                    r, c.T, preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST
                    if precision == "float32" else None,
                )
            else:
                sim = jnp.zeros(ok.shape, jnp.float32)
            top, idx = jax.lax.top_k(jnp.where(ok, sim, neg), k_eff)
            return jnp.where(top > neg, idx.astype(jnp.int32), -1)

        self._one = jax.jit(one)
        self._jnp = jnp

    def top(self, rows: np.ndarray, in_pool: np.ndarray) -> np.ndarray:
        jnp = self._jnp
        pool = jnp.asarray(_pad_rows(in_pool.astype(bool), self.pad))
        out = np.full((len(rows), self.k_eff), -1, np.int32)
        for lo in range(0, len(rows), _BLOCK):
            idx = np.full(_BLOCK, -1, np.int32)
            part = rows[lo:lo + _BLOCK]
            idx[:len(part)] = part
            block = {key: jnp.asarray(v[np.maximum(idx, 0)])
                     for key, v in self._host.items()}
            got = self._one(block, jnp.asarray(idx), self._cols, pool)
            out[lo:lo + len(part)] = np.asarray(got)[:len(part)]
        return out


def replay(specs: list[dict], ack_t, ticks, k: int, rev: bool,
           max_intervals: int = 2, precision: str = "float32",
           use_emb: bool = True) -> list[tuple[int, ...]]:
    """The matches a plain matcher forms: tuples of indices into `specs`,
    the ticket that searched last. `specs` are oldest first and `ack_t`
    (when each entered the pool) ascending; `ticks` are the times the
    interval loop ran."""
    n = len(specs)
    ack_t = np.asarray(ack_t, float)
    if n and np.any(np.diff(ack_t) < 0):
        raise ValueError("specs are not oldest first")
    enc = encode(specs)
    search = Candidates(enc, k, rev, precision, use_emb)
    min_c, max_c = enc["min_c"].tolist(), enc["max_c"].tolist()
    in_pool = np.zeros(n, bool)
    intervals = np.zeros(n, np.int32)
    searching: list[int] = []
    entered = 0
    groups = []
    for t in sorted(ticks):
        while entered < n and ack_t[entered] <= t:
            in_pool[entered] = True
            searching.append(entered)
            entered += 1
        actives = [i for i in searching if in_pool[i]]
        if not actives:
            searching = []
            continue
        cand = search.top(np.asarray(actives, np.int32), in_pool).tolist()
        free = in_pool.copy()
        searching = []
        for a, row in zip(actives, cand):
            if not free[a]:
                continue
            intervals[a] += 1
            last = intervals[a] >= max_intervals or min_c[a] == max_c[a]
            if not last:
                searching.append(a)
            got = []
            for j in row:
                if j < 0 or len(got) + 1 == max_c[a]:
                    break
                if free[j]:
                    got.append(j)
            size = len(got) + 1
            if size < 2 or not (
                size == max_c[a] or (last and size >= min_c[a])
            ) or any(not min_c[j] <= size <= max_c[j] for j in got):
                continue
            for j in got:
                free[j] = False
            free[a] = False
            groups.append(tuple(got) + (a,))
        in_pool = free
    return groups


def mean_pair_similarity(specs: list[dict], groups) -> float | None:
    """Mean, over matches, of the mean embedding dot product over all
    pairs of a match's members (f64)."""
    if not groups or not has_embeddings(specs):
        return None
    emb = np.stack([np.asarray(s["emb"], np.float64) for s in specs])
    total = 0.0
    for g in groups:
        e = emb[list(g)]
        gram = e @ e.T
        m = len(g)
        total += (gram.sum() - np.trace(gram)) / (m * (m - 1))
    return total / len(groups)
