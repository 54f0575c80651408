"""Class-routed batched QAC serving frontend (ISSUE 1 tentpole).

The fused ``qac_serve_step`` pays for BOTH engines on every lane: the
multi-term conjunctive scan and the single-term RMQ heap run for all B
queries and a branchless select throws one result away. The paper (§3.3)
notes single-term queries dominate production traffic, so that waste sits
exactly on the hot path.

This frontend routes on the host instead:

  1. **partition** the incoming batch by query class — single-term
     (``prefix_len == 0``) vs multi-term (``prefix_len > 0``);
  2. **pad** each class sub-batch up to a power-of-two bucket size (cyclic
     replication of real rows, so padding adds no new compile shapes and no
     pathological lanes);
  3. **dispatch** each sub-batch to *only* its engine under a per-
     (engine, bucket, k) jit cache — single-term additionally runs a short
     trip-budget engine with an exact full-budget fallback on the rare
     incomplete lane (see ``single_term_topk_bounded``);
  4. **scatter** results back into request order.

Results are bit-identical to ``qac_serve_step`` (tests/test_serve_frontend.py
checks element-for-element parity, including INF_DOCID padding and
empty-suffix-range queries).
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from ..compat import default_use_kernel
from ..core.types import INF_DOCID, MAX_TERMS
from ..core.builder import QACIndex
from ..obs.tracing import stage
from .qac import serve_single_term, serve_single_term_full, serve_multi_term

# VMEM ceiling for the intersect kernel's probe-list pad: beyond this the
# [P, L] block would not fit comfortably next to the candidate tile, so the
# frontend falls back to the XLA probe path for the multi-term class.
MAX_LIST_PAD = 1 << 15
# HBM budget for the [B, PMAX, list_pad] probe-list gather the kernel path
# materializes per multi-term dispatch, checked against the PER-BUCKET
# specialized list_pad (PR 3); buckets whose footprint still exceeds it
# fall back to the XLA probe path
MAX_MULTI_KERNEL_BYTES = 256 << 20


def route_classes(prefix_len):
    """Host-side classification: (single_rows, multi_rows) index arrays."""
    plen = np.asarray(prefix_len)
    return np.flatnonzero(plen <= 0), np.flatnonzero(plen > 0)


class QACFrontend:
    """Batched QAC completion with host-side class routing.

    One instance owns a jit cache keyed by (engine, bucket, k, list_pad);
    reuse it across requests so steady-state traffic never recompiles (the
    per-bucket ``list_pad`` adds at most log2(longest-list) variants per
    bucket). ``trips`` is the single-term pop budget (default k + 2); lanes
    that exhaust it fall back to the exact 2k-trip engine for the whole
    sub-batch. ``heap_kernel`` overrides the single-term engine's automatic
    VMEM-fit routing to the fused heap_topk kernel (None = auto).
    """

    def __init__(self, qidx: QACIndex, *, k: int = 10, tile: int = 128,
                 max_tiles: int | None = None, min_bucket: int = 8,
                 trips: int | None = None, use_kernel: bool | None = None,
                 interpret: bool | None = None,
                 heap_kernel: bool | None = None,
                 specialize_list_pad: bool = True,
                 postings_codec: str | None = None,
                 heap_kernel_max_bytes: int | None = None,
                 auditor=None):
        self.qidx = qidx
        self.k = k
        self.tile = tile
        self.max_tiles = max_tiles
        self.min_bucket = min_bucket
        self.trips = trips
        # postings device layout for the kernel routes (ISSUE 7):
        # None/"auto" = raw CSR preferred, compressed when only it fits the
        # heap-kernel VMEM ceiling; "ef"/"bitpack" force in-kernel decode.
        # An explicit codec also switches the multi-term intersect kernel to
        # the compressed probe route, which needs NO probe-list pad bound —
        # the packed index itself is the (static) VMEM footprint.
        self.postings_codec = postings_codec
        self.heap_kernel_max_bytes = heap_kernel_max_bytes
        self._explicit_packed = (
            postings_codec not in (None, "auto", "raw")
            and getattr(qidx.index, "packed", None) is not None)
        # per-bucket list_pad specialization (PR 3) mints one jit variant per
        # pow2 of the longest list a sub-batch probes — the right trade for
        # big offline batches, but ONLINE micro-batches are small and varied,
        # so the variant space stays open and every new pow2 is a compile
        # stall on the serving path. serve/runtime.py constructs frontends
        # with False: every multi-term dispatch uses the global worst-case
        # pad, closing the jit-variant space so steady state never recompiles
        self.specialize_list_pad = specialize_list_pad
        self.use_kernel = (default_use_kernel() if use_kernel is None
                           else use_kernel)
        self.interpret = interpret
        self.heap_kernel = heap_kernel    # None = static VMEM-fit auto-route
        # host-verified probe-list bound for the intersect kernel: the
        # longest posting list in the index, padded to a power of two. Only
        # the frontend can make this check (it routes on the host), which is
        # why the jit-only fused/striped paths keep the XLA probe path.
        # ``list_pad`` is the global worst case; each multi-term dispatch
        # re-derives the bound from the lists its sub-batch actually probes
        # (per-bucket specialization, see ``_multi_list_pad``).
        offs = np.asarray(qidx.index.offsets)
        self._list_lens = (np.diff(offs) if offs.size > 1
                           else np.zeros(1, np.int64))
        max_list = int(self._list_lens.max()) if offs.size > 1 else 1
        self.list_pad = 1 << max(1, (max_list - 1).bit_length())
        # None scans every driver list to its end: exact at any corpus size
        if max_tiles is None:
            self.max_tiles = max(1, -(-max_list // tile))
        self._cache = {}
        self.stats = {"requests": 0, "single_queries": 0, "multi_queries": 0,
                      "single_fallbacks": 0}
        # observability (ISSUE 10): the jit-variant auditor wraps every
        # newly-minted jit callable so its first invocation (where XLA
        # compiles) is timed + recorded, and post-freeze compiles are
        # flagged as closed-variant violations. None = unaudited.
        self.auditor = auditor
        self._route_desc: dict = {}

    def _multi_list_pad(self, pids, plen) -> int:
        """pow2 pad of the longest probe list THIS sub-batch can touch.

        The global ``self.list_pad`` covers the longest list in the whole
        index; most sub-batches only reference far shorter lists, so the
        [B, PMAX, list_pad] probe-list gather (and the kernel's VMEM block)
        shrinks accordingly. Capped at the global bound by construction.
        """
        if not self.specialize_list_pad:
            return self.list_pad
        valid = np.arange(pids.shape[1])[None, :] < plen[:, None]
        terms = np.clip(pids[valid], 0, len(self._list_lens) - 1)
        max_list = int(self._list_lens[terms].max()) if terms.size else 1
        return 1 << max(1, (max(max_list, 1) - 1).bit_length())

    # -- jit cache ------------------------------------------------------------
    def _bucket(self, n: int) -> int:
        return max(self.min_bucket, 1 << (n - 1).bit_length())

    def describe_route(self, engine: str, bucket: int = 0,
                       list_pad: int = 0) -> str:
        """Which kernel route a dispatch on ``engine`` actually takes, as a
        static host-side string ("heap_topk[raw]", "intersect[packed]",
        "xla_probes", ...). Mirrors the routing ladders in
        ``core.search.single_term_topk_bounded_batch`` (via
        ``describe_single_route``) and the multi-term ``use_k`` gate in
        ``_get`` — routing is static per (engine, bucket, list_pad), so
        the answer is cached."""
        ck = (engine, bucket, list_pad)
        desc = self._route_desc.get(ck)
        if desc is None:
            if engine in ("single", "single_full"):
                from ..core.search import describe_single_route

                desc = describe_single_route(
                    self.qidx.index, self.qidx.rmq_minimal,
                    use_kernel=self.use_kernel,
                    heap_kernel=self.heap_kernel,
                    postings_codec=self.postings_codec,
                    heap_kernel_max_bytes=self.heap_kernel_max_bytes)
            elif engine == "multi":
                # keep in sync with the use_k gate in _get below
                if self.use_kernel and self._explicit_packed:
                    desc = "intersect[packed]"
                elif (self.use_kernel and list_pad <= MAX_LIST_PAD
                        and bucket * MAX_TERMS * list_pad * 4
                        <= MAX_MULTI_KERNEL_BYTES):
                    desc = "intersect[raw]"
                else:
                    desc = "xla_probes"
            else:
                desc = engine
            self._route_desc[ck] = desc
        return desc

    def _get(self, engine: str, bucket: int, k: int, list_pad: int = 0):
        key = (engine, bucket, k, list_pad)
        fn = self._cache.get(key)
        if fn is None:
            # the index is an ARGUMENT of every jitted engine, never a
            # closure: a closed-over device array is baked into the
            # executable as a constant, one copy per jit variant (at the
            # declared size, GBs of host and device memory per variant).
            # Each engine's function name names its XLA module
            # (``jit_qac_single``, ``jit_qac_single_full``,
            # ``jit_qac_multi``) in a device trace
            if engine == "single":
                def qac_single(qidx, suf, slen):
                    out, done = serve_single_term(
                        qidx, suf, slen, k=k, trips=self.trips,
                        use_kernel=self.use_kernel, interpret=self.interpret,
                        heap_kernel=self.heap_kernel,
                        postings_codec=self.postings_codec,
                        heap_kernel_max_bytes=self.heap_kernel_max_bytes)
                    return out, jnp.all(done)   # scalar: one tiny host sync

                jitted = jax.jit(qac_single)
            elif engine == "single_full":
                def qac_single_full(qidx, suf, slen):
                    return serve_single_term_full(
                        qidx, suf, slen, k=k, use_kernel=self.use_kernel,
                        interpret=self.interpret,
                        heap_kernel=self.heap_kernel,
                        postings_codec=self.postings_codec,
                        heap_kernel_max_bytes=self.heap_kernel_max_bytes)

                jitted = jax.jit(qac_single_full)
            elif engine == "multi":
                # the compressed probe route replaces the [B, P, L] gather
                # with the whole packed index, so the list_pad/HBM gates
                # don't apply to it
                use_k = self.use_kernel and (
                    self._explicit_packed
                    or (list_pad <= MAX_LIST_PAD
                        and bucket * MAX_TERMS * list_pad * 4
                        <= MAX_MULTI_KERNEL_BYTES))

                def qac_multi(qidx, pids, plen, suf, slen):
                    return serve_multi_term(
                        qidx, pids, plen, suf, slen, k=k, tile=self.tile,
                        max_tiles=self.max_tiles, use_kernel=use_k,
                        interpret=self.interpret, list_pad=list_pad,
                        probe_iters=list_pad.bit_length(),
                        postings_codec=self.postings_codec)

                jitted = jax.jit(qac_multi)
            else:
                raise ValueError(engine)
            fn = functools.partial(jitted, self.qidx)
            if self.auditor is not None:
                fn = self.auditor.wrap(
                    key, fn, label=self.describe_route(engine, bucket,
                                                       list_pad))
            self._cache[key] = fn
        return fn

    def _k_bucket(self, ki: int) -> int:
        """jit-cache k snap (ISSUE 4 satellite). The frontend's default k
        stays exact — the common case never pays a bigger trip budget — and
        every other requested k rounds up to a power of two, so the long
        tail of large-k requests shares a handful of jit variants instead
        of minting one per distinct k. ``_complete_per_k`` groups rows by
        this bucket before dispatch, so a k=100 straggler no longer drags
        the whole batch's single-term trip budget up with it."""
        ki = int(ki)
        if ki == self.k:
            return ki
        return 1 << max(0, (ki - 1).bit_length())

    # -- serving --------------------------------------------------------------
    # A call is timed in stages (obs/tracing.py): ``frontend.route`` (class
    # partition, bucket padding, list-pad bound, and the rows' return to
    # request order), then per class the jitted call until it returns
    # (``engine.<e>.launch``, attrs = its jit key) and the blocking readback
    # (``engine.<e>.sync``)
    def _run(self, engine: str, bucket: int, k: int, list_pad: int, args):
        with stage(f"engine.{engine}.launch", bucket=bucket, k=k,
                   list_pad=list_pad):
            res = self._get(engine, bucket, k, list_pad)(*args)
        with stage(f"engine.{engine}.sync"):
            if engine != "single":
                return np.asarray(res)
            res, all_done = res
            if bool(all_done):
                return np.asarray(res)
        # a lane needed more than `trips` pops (duplicate-docid run):
        # recompute the sub-batch with the exact full-budget engine
        self.stats["single_fallbacks"] += 1
        return self._run("single_full", bucket, k, 0, args)

    def complete(self, prefix_ids, prefix_len, suffix_chars, suffix_len, *,
                 k: int | np.ndarray | None = None):
        """Routed batched Complete(): -> host docids int32[B, K] (INF padded),
        in the original request order.

        ``k`` may be a scalar (K = k, the classic contract) or a per-request
        int array (ISSUE 4 satellite): K = max(k), row i holds its exact
        k[i]-result in columns [0, k[i]) and INF_DOCID beyond — bit-identical
        to a scalar call at k[i], because the engines' top-k is prefix-stable
        (the first j results of a k-result are the j-result for j <= k).
        Rows are grouped by ``_k_bucket`` so tail ks share jit variants and
        never inflate the default-k trip budget.

        Inputs may be device or host arrays. The result lives on the host (the
        scatter-back is a host op and serving consumers read results there);
        wrap in ``jnp.asarray`` if device residency is needed.
        """
        k = self.k if k is None else k
        karr = np.asarray(k)
        if karr.ndim:
            karr = karr.astype(np.int64).reshape(-1)
            if karr.size == 0:
                return np.full((0, 0), INF_DOCID, np.int32)
            # collapse to the scalar path only for the frontend's default k:
            # a uniform TAIL k must still go through the bucketed path, or
            # every distinct uniform k would mint its own raw jit variant —
            # reopening the variant space the buckets exist to close
            if bool((karr == self.k).all()):
                return self._complete_scalar(prefix_ids, prefix_len,
                                             suffix_chars, suffix_len,
                                             self.k)
            return self._complete_per_k(prefix_ids, prefix_len, suffix_chars,
                                        suffix_len, karr)
        return self._complete_scalar(prefix_ids, prefix_len, suffix_chars,
                                     suffix_len, int(karr))

    def _complete_per_k(self, prefix_ids, prefix_len, suffix_chars,
                        suffix_len, karr):
        """Mixed-k batch: dispatch each pow2 k-bucket's rows separately."""
        pids = np.asarray(prefix_ids)
        plen = np.asarray(prefix_len)
        suf = np.asarray(suffix_chars)
        slen = np.asarray(suffix_len)
        B = plen.shape[0]
        kmax = int(karr.max())
        out = np.full((B, kmax), INF_DOCID, np.int32)
        buckets = np.asarray([self._k_bucket(ki) for ki in karr])
        for kb in np.unique(buckets):
            idx = np.flatnonzero(buckets == kb)
            sub = np.asarray(self._complete_scalar(
                pids[idx], plen[idx], suf[idx], slen[idx], int(kb)))
            w = min(int(kb), kmax)
            cols = np.arange(w)
            out[idx[:, None], cols[None, :]] = np.where(
                cols[None, :] < karr[idx][:, None], sub[:, :w], INF_DOCID)
        return out

    def _complete_scalar(self, prefix_ids, prefix_len, suffix_chars,
                         suffix_len, k: int):
        with stage("frontend.route"):
            plan = self._route(prefix_ids, prefix_len, suffix_chars,
                               suffix_len, k)
        return self._serve(*plan)

    def _route(self, prefix_ids, prefix_len, suffix_chars, suffix_len,
               k: int):
        """Host-side class routing -> (B, k, calls): per class present, one
        (engine, bucket, list_pad, inputs, rows) call; ``rows`` is None when
        the batch goes to its engine as it is."""
        plen = np.asarray(prefix_len)
        B = plen.shape[0]
        single_rows, multi_rows = route_classes(plen)
        self.stats["requests"] += 1
        self.stats["single_queries"] += int(single_rows.size)
        self.stats["multi_queries"] += int(multi_rows.size)

        # class-pure batch already at a bucket size: dispatch inputs as-is,
        # no padding copies (the common production case of a class-batched
        # upstream queue). The multi path still reads prefix_ids on the host
        # for the per-bucket list_pad — free when the caller passes
        # parse_queries' numpy output, a device sync otherwise
        if single_rows.size == B and self._bucket(B) == B:
            return B, k, [("single", B, 0, (suffix_chars, suffix_len), None)]
        if multi_rows.size == B and self._bucket(B) == B:
            lp = self._multi_list_pad(np.asarray(prefix_ids), plen)
            return B, k, [("multi", B, lp, (prefix_ids, plen, suffix_chars,
                                            suffix_len), None)]

        pids = np.asarray(prefix_ids)
        suf = np.asarray(suffix_chars)
        slen = np.asarray(suffix_len)
        calls = []
        if single_rows.size:
            pad = np.resize(single_rows, self._bucket(single_rows.size))
            calls.append(("single", len(pad), 0, (suf[pad], slen[pad]),
                          single_rows))
        if multi_rows.size:
            pad = np.resize(multi_rows, self._bucket(multi_rows.size))
            lp = self._multi_list_pad(pids[pad], plen[pad])
            calls.append(("multi", len(pad), lp,
                          (pids[pad], plen[pad], suf[pad], slen[pad]),
                          multi_rows))
        return B, k, calls

    def _serve(self, B: int, k: int, calls):
        """Each class's engine call, then its rows back into request order."""
        res = [self._run(engine, bucket, k, lp, args)
               for engine, bucket, lp, args, _ in calls]
        if len(calls) == 1 and calls[0][4] is None:
            return res[0]
        with stage("frontend.route"):
            out = np.full((B, k), INF_DOCID, np.int32)
            for (_, _, _, _, rows), r in zip(calls, res):
                out[rows] = r[: rows.size]
        return out
