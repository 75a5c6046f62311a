"""ReplicatedIndex: scale-out serving over a device mesh.

The paper makes multi-vector indexes small enough to be *practical*;
this layer makes serving them *scale*: one logical index becomes
``n_replicas`` replica groups, each group placing its shards across
devices (launch/mesh.make_serve_mesh axes ("replica", "shard")), with
the serving engine's router (launch/engine.py) fanning each microbatch
to a replica lane. Three placement regimes, all bitwise-identical to
the single-device ``search_batch`` (ids + scores + tie order):

  * **Generic dispatch** (any backend): replica ``r``'s shards probe
    under their placed devices (``ShardedIndex.place``) — stage 1 stays
    host numpy, stage 2 + the per-shard local top-k run per device, and
    the merge moves only [Nq, k] blocks device-to-device (no host
    round-trip per shard; see core/sharded.py).
  * **SPMD flat scan** (flat backend, one device per live shard): the
    whole group's dense corpus scan + local top-k + merge collective is
    ONE ``shard_map`` program over a 1-D ("shard",) mesh — doc tensors
    device-put with the ``sharding.api.serve_rules`` logical-axis specs
    ("docs" -> shard axis, queries replicated), merged with a tiled
    ``all_gather`` whose axis order IS shard order, so the tie-order
    proof of the dispatch merge carries over unchanged.
  * **Degraded single-device**: fewer devices than cells — placement
    tiles round-robin (``serve_device_table``); everything still
    serves, bit-identical, with thread-level concurrency only.

Replicas may share ONE inner index object (``replicate`` — zero extra
host memory; device arrays are per-group only on the SPMD flat path) or
hold distinct copies (``from_dir`` — mmap reopens per replica, so each
group's lazy device caches commit to its own device row; host pages
stay shared via the page cache). Mutation is a serving anti-pattern
here: ``delete`` fans to every copy and drops compiled plans; ``add``
requires the shared-inner form — rebuild + hot-swap is the supported
path for index growth (the engine's watcher re-places on every swap).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.index import MultiVectorIndex
from repro.core.maxsim import PendingTopK, maxsim_all_docs, topk_dispatch
from repro.core.sharded import ShardedIndex
from repro.launch.mesh import (distinct_row, make_shard_mesh,
                               serve_device_table)
from repro.sharding.api import logical_spec, mesh_context, serve_rules


def _parts(inner) -> List[Tuple[int, MultiVectorIndex]]:
    """(global doc base, shard) pairs — a monolithic index is one part."""
    if isinstance(inner, ShardedIndex):
        return list(zip(inner.doc_base, inner.shards))
    return [(0, inner)]


class _FlatPlan:
    """One replica group's flat corpus scan as a single SPMD program.

    Doc tensors are stacked [S, Ndp, Lp, dim] (every live shard padded
    to the group max — MaxSim is pad-invariant: masked tokens score
    -inf into a max, padded doc rows are live-masked to -inf) and
    device_put sharded over a 1-D ("shard",) mesh with the
    ``serve_rules`` logical specs. The program computes each shard's
    dense scores + local top-k, shifts to global ids, and merges with a
    tiled ``all_gather`` (axis order = shard order); the host epilogue
    (``topk_dispatch``) reduces the replicated [Nq, S*kk] block to the
    final [Nq, k] — identical math to the dispatch merge, one XLA
    dispatch instead of S.
    """

    def __init__(self, parts: Sequence[Tuple[int, MultiVectorIndex]],
                 row: Sequence):
        from jax.sharding import NamedSharding
        self.mesh = make_shard_mesh(row)
        self.merge_device = list(row)[0]
        S = len(parts)
        dim = parts[0][1].dim
        views = []
        for base, shard in parts:
            d, m = shard.store.padded()
            views.append((base, np.asarray(d), np.asarray(m),
                          np.asarray(shard._live(), bool)))
        Ndp = max(v[1].shape[0] for v in views)
        Lp = max(v[1].shape[1] for v in views)
        D = np.zeros((S, Ndp, Lp, dim), np.float32)
        M = np.zeros((S, Ndp, Lp), bool)
        LV = np.zeros((S, Ndp), bool)
        B = np.zeros((S,), np.int32)
        for i, (base, d, m, lv) in enumerate(views):
            D[i, :d.shape[0], :d.shape[1]] = d
            M[i, :m.shape[0], :m.shape[1]] = m
            LV[i, :lv.shape[0]] = lv
            B[i] = base
        with mesh_context(self.mesh, serve_rules()):
            specs = (logical_spec("docs", None, None, None),
                     logical_spec("docs", None, None),
                     logical_spec("docs", None),
                     logical_spec("docs"))
        self._specs = specs
        put = lambda x, sp: jax.device_put(  # noqa: E731
            x, NamedSharding(self.mesh, sp))
        self.d = put(D, specs[0])
        self.m = put(M, specs[1])
        self.live = put(LV, specs[2])
        self.base = put(B, specs[3])
        self.n_docs_padded = Ndp
        self._fns: Dict[int, object] = {}

    def _fn(self, kk: int):
        if kk in self._fns:
            return self._fns[kk]
        from jax.sharding import PartitionSpec as P
        sd, sm, sl, sb = self._specs

        def body(d, m, lv, b, q, qm):
            d, m, lv, b = d[0], m[0], lv[0], b[0]
            s = maxsim_all_docs(q, qm, d, m)            # [Nq, Ndp]
            s = jnp.where(lv[None, :], s, -jnp.inf)
            ts, ti = jax.lax.top_k(s, kk)
            gi = ti.astype(jnp.int32) + b
            return (jax.lax.all_gather(ts, "shard", axis=1, tiled=True),
                    jax.lax.all_gather(gi, "shard", axis=1, tiled=True))

        fn = jax.jit(jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(sd, sm, sl, sb, P(), P()),
            out_specs=(P(), P()), check_vma=False))
        self._fns[kk] = fn
        return fn

    def search(self, qs: np.ndarray, q_mask, k: int) -> PendingTopK:
        qs = jnp.asarray(np.asarray(qs, np.float32))
        qm = (jnp.ones(qs.shape[:2], bool) if q_mask is None
              else jnp.asarray(np.asarray(q_mask, bool)))
        kk = min(k, self.n_docs_padded)
        ts, gi = self._fn(kk)(self.d, self.m, self.live, self.base,
                              qs, qm)
        # outputs are mesh-replicated; pull one copy of each to the
        # merge device for the (single-device) final top-k epilogue
        ts = jax.device_put(ts, self.merge_device)
        gi = jax.device_put(gi, self.merge_device)
        return topk_dispatch(ts, gi, k)


class ReplicatedIndex:
    """Replica groups + device-placed shards behind one index API.

    ``search_batch`` (parity surface) routes to replica 0;
    ``search_batch_on(r, ...)`` is the router's per-lane entry — every
    replica returns bitwise-identical results, so routing is purely a
    throughput decision. Construction: ``replicate`` shares one inner
    index across groups, ``from_dir`` reopens the artifact per group
    (mmap) so each group owns its device caches, dividing the auto
    probe-thread width across lanes (``ShardSpec.probe_threads`` pins
    it explicitly).
    """

    def __init__(self, replicas: Sequence, *, own_inner: bool = False,
                 device_table: Optional[List[List]] = None,
                 use_shard_map: Optional[bool] = None):
        self._inners = list(replicas)
        assert self._inners, "need at least one replica"
        first = self._inners[0]
        for ix in self._inners[1:]:
            assert ix.backend == first.backend, "replica backend mismatch"
            assert ix.n_docs == first.n_docs, "replica corpus mismatch"
        self.n_replicas = len(self._inners)
        self.own_inner = own_inner
        # None = auto (flat backend, >=2 live shards, one device each);
        # False = dispatch only; True = force when buildable (tests)
        self.use_shard_map = use_shard_map
        self._distinct = (len({id(ix) for ix in self._inners})
                          == self.n_replicas)
        n_shards = max(len(_parts(first)), 1)
        self.device_table = (list(device_table) if device_table is not None
                             else serve_device_table(self.n_replicas,
                                                     n_shards))
        assert len(self.device_table) == self.n_replicas
        self._multi_device = len(jax.devices()) > 1
        self._plans: Dict[int, Optional[_FlatPlan]] = {}
        self._plan_lock = threading.Lock()
        self._closed = False
        self._place_all()

    # -------------------------------------------------------- construction
    @classmethod
    def replicate(cls, index, n_replicas: int = 1,
                  own_inner: bool = False, **kw) -> "ReplicatedIndex":
        """Replica groups over ONE shared inner index (no host copies).
        Per-group device placement applies only on the SPMD flat path
        (which owns its device arrays); other backends share the
        group-0 placement and scale via lane concurrency."""
        assert n_replicas >= 1, n_replicas
        return cls([index] * int(n_replicas), own_inner=own_inner, **kw)

    @classmethod
    def from_dir(cls, path: str, n_replicas: int = 1, mmap: bool = True,
                 **kw) -> "ReplicatedIndex":
        """One mmap reopen per replica group: distinct index objects
        whose lazy device caches commit to their own device rows (host
        pages shared by the page cache). Auto probe-thread width is
        divided across groups so lanes x workers never oversubscribes;
        a ``ShardSpec.probe_threads`` pin recorded in the manifest is
        honored as-is."""
        from repro.core.persist import load_artifact
        assert n_replicas >= 1, n_replicas
        reps = []
        table = kw.pop("device_table", None)
        for r in range(int(n_replicas)):
            # load under the lane's device: arrays made at load time (the
            # codec tables) must live where that lane computes
            ctx = (jax.default_device(table[r][0]) if table is not None
                   else contextlib.nullcontext())
            with ctx:
                ix = load_artifact(path, mmap=mmap)
            if table is None:
                table = serve_device_table(int(n_replicas),
                                           max(len(_parts(ix)), 1))
            if (isinstance(ix, ShardedIndex) and n_replicas > 1
                    and ix.probe_threads_cfg == 0):
                ix.set_probe_threads(
                    max(1, ix.probe_threads // int(n_replicas)))
            reps.append(ix)
        return cls(reps, own_inner=True, device_table=table, **kw)

    def _place_all(self) -> None:
        if not self._multi_device:
            return                      # single device: placement is moot
        placed = set()
        for r, inner in enumerate(self._inners):
            if id(inner) in placed:
                continue                # shared inner: group-0 row wins
            placed.add(id(inner))
            if isinstance(inner, ShardedIndex):
                inner.place(self.device_table[r][:inner.n_shards])

    def _ctx(self, r: int):
        """Per-lane device context for MONOLITHIC inners — only safe
        when each lane owns its copy (a shared inner's caches commit to
        one device; pinning queries elsewhere would split the args of
        one jit call across devices)."""
        if (self._multi_device and self._distinct
                and not isinstance(self._inners[r], ShardedIndex)):
            return jax.default_device(self.device_table[r][0])
        return contextlib.nullcontext()

    # ------------------------------------------------------------- topology
    @property
    def inner(self):
        return self._inners[0]

    @property
    def backend(self) -> str:
        return self._inners[0].backend

    @property
    def dim(self) -> int:
        return self._inners[0].dim

    @property
    def n_docs(self) -> int:
        return self._inners[0].n_docs

    @property
    def n_shards(self) -> int:
        return len(_parts(self._inners[0]))

    def n_vectors(self) -> int:
        return self._inners[0].n_vectors()

    def nbytes(self) -> int:
        return self._inners[0].nbytes()

    def device_bytes(self) -> int:
        seen, total = set(), 0
        for ix in self._inners:
            if id(ix) not in seen:
                seen.add(id(ix))
                total += ix.device_bytes()
        return total

    # ----------------------------------------------------------------- CRUD
    def _invalidate(self) -> None:
        with self._plan_lock:
            self._plans.clear()

    def add(self, doc_vectors) -> np.ndarray:
        if self._distinct and self.n_replicas > 1:
            raise RuntimeError(
                "add() on a multi-copy ReplicatedIndex would desync the "
                "replicas — rebuild the artifact and hot-swap instead")
        ids = self._inners[0].add(doc_vectors)
        self._invalidate()
        return ids

    def delete(self, doc_ids) -> None:
        seen = set()
        for ix in self._inners:
            if id(ix) not in seen:
                seen.add(id(ix))
                ix.delete(doc_ids)
        self._invalidate()

    def set_probe_kernel(self, probe_kernel: str) -> None:
        """Fan the runtime-only plaid candidate-path toggle to every
        distinct inner (monolithic or sharded)."""
        seen = set()
        for ix in self._inners:
            if id(ix) in seen:
                continue
            seen.add(id(ix))
            if isinstance(ix, ShardedIndex):
                ix.set_probe_kernel(probe_kernel)
            else:
                from repro.core.plaid import PROBE_KERNELS
                assert probe_kernel in PROBE_KERNELS, probe_kernel
                ix.probe_kernel = probe_kernel

    # ----------------------------------------------------------------- plans
    def _plan_for(self, r: int) -> Optional[_FlatPlan]:
        if self.backend != "flat" or self.use_shard_map is False:
            return None
        with self._plan_lock:
            if r in self._plans:
                return self._plans[r]
            inner = self._inners[r]
            pos = [i for i, (_, s) in enumerate(_parts(inner))
                   if s.n_docs > 0]
            # modulo-tile: adds can grow the shard count past the table
            tbl = self.device_table[r]
            row = [tbl[i % len(tbl)] for i in pos]
            auto_ok = len(pos) >= 2 and self._multi_device
            ok = (bool(pos) and distinct_row(row)
                  and (auto_ok or self.use_shard_map is True))
            parts = [p for p in _parts(inner) if p[1].n_docs > 0]
            plan = _FlatPlan(parts, row) if ok else None
            self._plans[r] = plan
            return plan

    # ---------------------------------------------------------------- search
    def search_batch_on(self, replica: int, qs: np.ndarray, k: int = 10,
                        q_mask: Optional[np.ndarray] = None, *,
                        fetch: bool = True):
        """One replica lane's search — bitwise identical for every lane;
        the router picks ``replica`` for throughput, not results.
        ``fetch=False`` returns the result as dispatched, as
        ``MultiVectorIndex.search_batch`` has it."""
        r = int(replica) % self.n_replicas
        plan = self._plan_for(r)
        if plan is not None:
            out = plan.search(qs, q_mask, k)
        else:
            with self._ctx(r):
                out = self._inners[r].search_batch(qs, k=k, q_mask=q_mask,
                                                   fetch=False)
        return tuple(out) if fetch else out

    def search_batch(self, qs: np.ndarray, k: int = 10,
                     q_mask: Optional[np.ndarray] = None, *,
                     fetch: bool = True):
        """Parity surface: identical to the wrapped index's
        ``search_batch`` (routes through lane 0)."""
        return self.search_batch_on(0, qs, k=k, q_mask=q_mask, fetch=fetch)

    def search(self, q: np.ndarray, k: int = 10
               ) -> Tuple[np.ndarray, np.ndarray]:
        S, I = self.search_batch(np.asarray(q, np.float32)[None], k=k)
        valid = I[0] >= 0
        return S[0][valid], I[0][valid]

    def warm_shapes(self, qs: np.ndarray, k: int = 10) -> None:
        """Warm EVERY lane at this batch shape: plan lanes trace their
        SPMD program + epilogue, dispatch lanes run the full per-shard
        ladder warm on their placed devices — so a router mixing lanes
        mid-stream re-traces nothing (CompileCounter contract)."""
        qs = np.asarray(qs, np.float32)
        warmed = set()
        for r in range(self.n_replicas):
            plan = self._plan_for(r)
            if plan is not None:
                plan.search(qs, None, k).fetch()
                continue
            inner = self._inners[r]
            if id(inner) in warmed:
                continue
            warmed.add(id(inner))
            with self._ctx(r):
                inner.warm_shapes(qs, k=k)

    # -------------------------------------------------------------- lifecycle
    def close(self) -> None:
        """Drop compiled plans and (for ``own_inner`` constructions,
        e.g. watcher loads and ``from_dir``) release every distinct
        inner's resources — the hot-swap retire hook the engine calls
        so replica fleets don't strand probe pools across generations."""
        if self._closed:
            return
        self._closed = True
        self._invalidate()
        if not self.own_inner:
            return
        seen = set()
        for ix in self._inners:
            if id(ix) in seen:
                continue
            seen.add(id(ix))
            close = getattr(ix, "close", None)
            if close is not None:
                close()

    @property
    def closed(self) -> bool:
        return self._closed
