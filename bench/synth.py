"""The benchmark's own copy of the query-log and keystroke generators.

Copied from ``src/repro/text/synth.py`` (``generate_query_log`` and
``generate_keystroke_trace``) so that a change to the program cannot move
the yardstick. The query log keeps the original's distributions; its
vocabulary is drawn in whole arrays, and it also returns the term ids
it drew, which the plain reference (``bench/reference.py``) works from.
The keystroke generator keeps the original's per-session typing model
(exponential gaps, backspace runs, bounded Zipf target popularity), draws
a fixed set of sessions, lets the run's seed decide which session starts
when, and never rescales time.
"""
from __future__ import annotations

import dataclasses

import numpy as np

_ALPHA = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz", dtype=np.uint8)


@dataclasses.dataclass(frozen=True)
class LogParams:
    """Parameters of one synthetic scored query log (a configuration's
    ``generator`` group)."""

    n_queries: int
    vocab_size: int
    zipf_s: float = 1.07
    mean_terms: float = 3.0
    mean_term_chars: float = 7.0
    max_terms: int = 7


@dataclasses.dataclass
class QueryLog:
    """A generated log: the raw strings the program is built from, and the
    term ids behind them. ``vocab`` is sorted; ``term_idx`` holds, query
    after query, 0-based indices into it; ``n_terms[i]`` is the length of
    query i."""

    queries: list
    scores: np.ndarray
    vocab: list
    term_idx: np.ndarray
    n_terms: np.ndarray


def _make_vocab(rng: np.random.Generator, p: LogParams) -> list[str]:
    """``vocab_size`` distinct random lowercase words, sorted. The same
    distribution as the original's word-by-word loop, drawn in whole
    arrays (the original's loop takes a minute at millions of words)."""
    width = 16
    words = np.zeros(0, f"S{width}")
    while words.size < p.vocab_size:
        n = p.vocab_size - words.size
        lens = np.clip(rng.poisson(p.mean_term_chars, n), 2, width)
        chars = _ALPHA[rng.integers(0, 26, int(lens.sum()))]
        grid = np.zeros((n, width), np.uint8)
        grid[np.arange(width)[None, :] < lens[:, None]] = chars
        words = np.union1d(words, grid.view(f"S{width}").ravel())
    return [w.decode() for w in words.tolist()]


def generate_query_log(p: LogParams, seed) -> QueryLog:
    """Zipf term reuse over a random vocabulary, Poisson query lengths and
    Zipf frequency scores; duplicates are possible (the builder keeps the
    highest score). ``seed`` is anything ``np.random.default_rng`` takes."""
    rng = np.random.default_rng(seed)
    vocab = _make_vocab(rng, p)
    V = len(vocab)
    # Zipf ranks over a shuffled vocab so lexicographic and popularity
    # order differ
    perm = rng.permutation(V)
    probs = 1.0 / np.arange(1, V + 1) ** p.zipf_s
    probs /= probs.sum()
    n_terms = np.clip(rng.poisson(p.mean_terms - 1, p.n_queries) + 1, 1,
                      p.max_terms)
    idx = perm[rng.choice(V, size=int(n_terms.sum()), p=probs)]
    words = [vocab[i] for i in idx.tolist()]
    ends = np.cumsum(n_terms).tolist()
    queries = [" ".join(words[e - nt:e])
               for e, nt in zip(ends, n_terms.tolist())]
    scores = rng.zipf(1.2, size=p.n_queries).astype(np.float64)
    return QueryLog(queries, scores, vocab, idx.astype(np.int32),
                    n_terms.astype(np.int32))


@dataclasses.dataclass(frozen=True)
class TrafficParams:
    """One traffic mix (a file under ``bench/traffic``).

    A fixed set of sessions, drawn once from the configuration's dataset
    seed: each types one target, drawn by bounded Zipf popularity over the
    corpus, keystroke by keystroke, with exponential gaps and backspace
    runs; ``first_term`` cuts every target at its first space, so that all
    traffic is single-term. Sessions are added until ``request_rate`` times
    the window's seconds keystrokes fall due in the window. The session
    starts are uniform over the warm-up and the window (a Poisson process,
    conditioned on its count), and the run's seed decides which session
    takes which start: every seed offers the same work, in another order."""

    request_rate: float
    mean_keystroke_ms: float = 150.0
    p_backspace: float = 0.06
    max_backspace: int = 3
    popularity_zipf_s: float = 1.05
    first_term: bool = False


def _session(rng, target: str, p: TrafficParams):
    """One session's keystrokes: (offset from its start in us, query)."""
    events, t, n = [], 0.0, 1
    while n <= len(target):
        t += rng.exponential(p.mean_keystroke_ms) * 1e3
        events.append((t, target[:n]))
        if 1 < n < len(target) and rng.random() < p.p_backspace:
            for _ in range(int(rng.integers(1, p.max_backspace + 1))):
                if n <= 1:
                    break
                n -= 1
                t += rng.exponential(p.mean_keystroke_ms / 2) * 1e3
                events.append((t, target[:n]))
        n += 1
    return events


def draw_sessions(pool_size: int, target_of, p: TrafficParams,
                  warm_s: float, seconds: float, seed):
    """-> (sessions, starts): each session's keystrokes as (offset from its
    start in us, query), and the session starts (us), uniform over
    [0, warm_s + seconds). Sessions are added until ``request_rate`` times
    ``seconds`` keystrokes fall due in the window (session i at start i).

    ``target_of(i)`` gives the string of pool entry i (the pool is the
    deduplicated corpus); only the drawn targets are ever built."""
    rng = np.random.default_rng(seed)
    span_us = (warm_s + seconds) * 1e6
    w0 = warm_s * 1e6
    want = round(p.request_rate * seconds)
    perm = rng.permutation(pool_size)
    # bounded Zipf over popularity ranks, drawn by its inverse CDF
    cdf = np.cumsum(1.0 / np.arange(1, pool_size + 1) ** p.popularity_zipf_s)
    cdf /= cdf[-1]
    sessions, starts, in_window = [], [], 0
    while in_window < want:
        picks = perm[np.minimum(np.searchsorted(cdf, rng.random(1024)),
                                pool_size - 1)]
        for t, qi in zip(rng.uniform(0.0, span_us, 1024).tolist(),
                         picks.tolist()):
            target = target_of(qi)
            if p.first_term:
                target = target.split(" ", 1)[0]
            sessions.append(_session(rng, target, p))
            starts.append(t)
            in_window += sum(w0 <= t + dt < span_us
                             for dt, _ in sessions[-1])
            if in_window >= want:
                break
    return sessions, starts


def schedule(sessions, starts, span_s: float, seed):
    """-> list[(t_us, session, partial_query)] before ``span_s``, sorted by
    time, with the seed deciding which session takes which start."""
    order = np.random.default_rng(seed).permutation(len(sessions))
    span_us = span_s * 1e6
    events = [(starts[j] + dt, s, q)
              for s, j in enumerate(order.tolist())
              for dt, q in sessions[s] if starts[j] + dt < span_us]
    events.sort(key=lambda e: (e[0], e[1]))
    return events
