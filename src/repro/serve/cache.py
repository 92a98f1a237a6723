"""Paged per-request propagation-state pool for the serving engine.

Slot/cache lifecycle contract (DESIGN.md §9): the pool owns ONE batched
cache pytree (`init_lm_cache(cfg, n_slots, max_len)`) whose batch axis is
the slot id.  A request's life cycle against the pool is

    slot = pool.alloc()          # admission — None when the batch is full
    pool.commit(slot, cache_1)   # scatter a finished (batch-1) prefill in
    pool.caches / pool.update()  # batched decode reads + writes all slots
    pool.free(slot)              # retirement — slot id returns to the pool

``alloc`` after ``free`` MUST be clean: ``commit`` overwrites every cache
leaf's slot row, so a reused slot never observes its previous occupant's
state (pinned by tests/test_serve_engine.py::test_cache_pool_*).  Unlike a
KV cache, the GSPN/SSM leaves are O(1) in sequence length — paging a
request in or out moves a compact recurrent state, not an O(L) history —
which is what makes per-request admission/retirement cheap (LASP-2
observation, PAPERS.md).

`update_cache_slots` lives here (moved from ``serve.engine``, which
re-exports it for compatibility): it is the scatter primitive ``commit``
is built on, and is layout-aware — prelude/shared stages stack caches as
(n, B, ...), unit stages as (n_units, n, B, ...).
"""

from __future__ import annotations

import collections
import functools
import hashlib
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models import lm as lm_mod


#: Cache leaves that hold attention history (one row per token, written
#: once); every other floating leaf is recurrent state (GSPN rows, Mamba-2
#: SSM state and conv tail), rewritten at every step.
KV_LEAVES = ("k", "v")


def narrow_state(tree, state_dtype):
    """Cast the attention KV leaves of a cache pytree to ``state_dtype``
    (DESIGN.md §10).  Recurrent state keeps the dtype it is computed in,
    the f32 carry dtype: it integrates every earlier token, so rounding it
    at rest would re-inject error at every step, where a KV row is rounded
    once.  Integer leaves (lengths, positions) pass through.  The single
    definition of the at-rest narrowing rule — the pool's init/update and
    the engine's jitted decode all route through it."""
    if state_dtype is None:
        return tree

    def narrow(path, a):
        name = getattr(path[-1], "key", None)
        if name in KV_LEAVES and jnp.issubdtype(a.dtype, jnp.floating):
            return a.astype(state_dtype)
        return a
    return jax.tree_util.tree_map_with_path(narrow, tree)


def update_cache_slots(cfg, caches, new_caches, slots):
    """Scatter ``new_caches`` (batch = len(slots)) into ``caches`` at the
    given slot indices.  Batch-axis position depends on the stage kind:
    prelude/shared stages stack (n, B, ...), unit stages (n_units, n, B...)."""
    slots = jnp.asarray(slots, jnp.int32)

    def upd(axis):
        def f(big, new):
            bigm = jnp.moveaxis(big, axis, 0)
            newm = jnp.moveaxis(new, axis, 0)
            return jnp.moveaxis(bigm.at[slots].set(newm.astype(bigm.dtype)),
                                0, axis)
        return f

    prelude_keys = {f"s{si}_{kind}" for si, (w, kind, n)
                    in enumerate(cfg.stages()) if w == "prelude"}
    out = {}
    for key, sub in caches.items():
        if key in prelude_keys or key == "shared_attn":
            axis = 1
        else:
            axis = 2
        out[key] = jax.tree.map(upd(axis), sub, new_caches[key])
    return out


# The pool's zeroed caches, made by one program straight into their final
# buffers (eagerly, each stage's stacking would copy it once more).
empty_caches = jax.jit(lm_mod.init_lm_cache, static_argnums=(0, 1, 2))


@functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
def commit_slots(cfg, caches, new_caches, slots):
    """``update_cache_slots`` as one compiled scatter that writes the
    donated pool in place (an eager scatter would hold the old and the new
    pool at once); compiled once per configuration and shape, whichever
    pool calls it."""
    return update_cache_slots(cfg, caches, new_caches, slots)


def _prefix_metrics():
    """Prefix-cache counters in the process-global registry (get-or-create
    per access, mirroring the engine's ``_serve_metrics`` pattern)."""
    return {
        "hits": obs.counter("serve_prefix_hits_total",
                            "prefill admissions resumed from a cached "
                            "prefix state"),
        "misses": obs.counter("serve_prefix_misses_total",
                              "prefill admissions with no usable prefix"),
        "reused": obs.counter("serve_prefix_tokens_reused_total",
                              "prompt tokens served from cached state "
                              "instead of recomputed"),
        "evicted": obs.counter("serve_prefix_evictions_total",
                               "prefix entries dropped by the LRU bound"),
    }


class PrefixStateCache:
    """Token-prefix → boundary-state cache for chunked prefill
    (DESIGN.md §15).

    The GSPN propagation state at a fold-row boundary is O(W) and
    resumable (PR 3 proved chunk-chain ≡ one-shot), so a prompt-prefix
    cache needs no new numerics: store the engine's in-flight batch-1
    cache pytree at a chunk-aligned offset ``k`` (chunk offsets are
    snapped to the fold width, so ``k`` always sits on a grid-row
    boundary), and a later prompt sharing those ``k`` tokens re-enters
    ``lm_prefill_chunk`` at offset ``k`` through the exact
    ``boundary=chunk_resume`` path a cold chain uses.

    Keys are the SHA-1 of the prefix's int32 token bytes; the exact
    token array is stored alongside and verified on lookup, so a hash
    collision degrades to a miss, never to wrong state.  Entries hold
    jax arrays (immutable — sharing with an in-flight prefill is safe);
    ``lookup`` returns a fresh *container* copy so the engine's dict
    bookkeeping never aliases the stored entry.  Bounded LRU: entries
    are full per-slot cache pytrees (O(max_len) attention KV), so the
    default capacity is deliberately small.  Thread-safe — router tiers
    share ONE instance across replica worker threads.
    """

    def __init__(self, capacity: int = 8):
        self.capacity = capacity
        self._entries: collections.OrderedDict = collections.OrderedDict()
        self._lock = threading.Lock()

    @staticmethod
    def _key(tokens: np.ndarray) -> str:
        return hashlib.sha1(
            np.ascontiguousarray(tokens, np.int32).tobytes()).hexdigest()

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def nbytes(self) -> int:
        """Total bytes of every cached state pytree (capacity planning)."""
        with self._lock:
            return sum(int(a.size) * a.dtype.itemsize
                       for _toks, tree in self._entries.values()
                       for a in jax.tree.leaves(tree))

    def insert(self, prefix_tokens, cache_tree):
        """Store ``cache_tree`` (the engine's batch-1 prefill cache after
        consuming exactly ``prefix_tokens``).  The caller guarantees the
        offset is chunk-aligned; re-inserting an existing prefix just
        refreshes its LRU position."""
        toks = np.ascontiguousarray(prefix_tokens, np.int32)
        key = self._key(toks)
        with self._lock:
            if key in self._entries:
                self._entries.move_to_end(key)
                return
            self._entries[key] = (toks, cache_tree)
            while len(self._entries) > self.capacity:
                self._entries.popitem(last=False)
                _prefix_metrics()["evicted"].inc()

    def lookup(self, prompt, chunk: int):
        """Longest cached chunk-aligned proper prefix of ``prompt``.
        Returns ``(k, cache_tree_copy)`` or None.  ``k`` is capped at
        ``len(prompt) - 1`` so at least one prompt token remains to
        prefill — the final chunk must produce the first-token logits."""
        prompt = np.ascontiguousarray(prompt, np.int32)
        m = _prefix_metrics()
        k = ((len(prompt) - 1) // chunk) * chunk
        while k >= chunk:
            with self._lock:
                ent = self._entries.get(self._key(prompt[:k]))
                if ent is not None and np.array_equal(ent[0], prompt[:k]):
                    self._entries.move_to_end(self._key(prompt[:k]))
                    m["hits"].inc()
                    m["reused"].inc(k)
                    # fresh containers, shared (immutable) leaves
                    return k, jax.tree.map(lambda a: a, ent[1])
            k -= chunk
        m["misses"].inc()
        return None


class StateCachePool:
    """Fixed-capacity pool of per-request propagation-state pages.

    One page == one batch row of the engine-wide cache pytree.  The free
    list is LIFO so tests can pin reuse; ``alloc`` returns ``None`` on
    exhaustion (the scheduler's backpressure signal — requests then wait
    in the admission queue).

    ``state_dtype`` (DESIGN.md §10) narrows the attention KV pages to the
    given dtype at rest; recurrent state (GSPN rows, Mamba-2 SSM state and
    conv tail) stays at the f32 carry dtype, and integer leaves such as
    lengths/positions are untouched (``narrow_state``).  bf16 KV halves the
    bytes of a KV pool, which doubles the decode batch that fits a fixed
    memory budget; ``commit``/``update`` casts on scatter, and every
    consumer already lifts state back to f32 compute at use, so narrowing
    is a storage decision, not a compute one.  Two kinds of state live
    side by side in one pool: O(1) recurrent state and O(``max_len``) KV,
    both indexed by the slot's batch row.
    """

    def __init__(self, cfg, n_slots: int, max_len: int, state_dtype=None):
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.state_dtype = (None if state_dtype is None
                            else jnp.dtype(state_dtype))
        self.caches = narrow_state(empty_caches(cfg, n_slots, max_len),
                                   self.state_dtype)
        self._free = list(range(n_slots - 1, -1, -1))   # pop() yields slot 0
        self._used = set()
        for kind, n in self.bytes_by_kind().items():
            obs.gauge(f"serve_state_bytes_{kind}",
                      f"bytes of the state pool's {kind} leaves").set(n)

    def bytes_by_kind(self) -> dict:
        """Pool bytes by leaf kind: ``kv`` (attention K/V pages), ``ssm``
        (Mamba-2 state), ``conv`` (Mamba-2 conv tail) and ``other``
        (GSPN rows, xLSTM state, lengths and positions)."""
        out = {"kv": 0, "ssm": 0, "conv": 0, "other": 0}

        def add(path, a):
            name = getattr(path[-1], "key", None)
            kind = ("kv" if name in KV_LEAVES else
                    name if name in ("ssm", "conv") else "other")
            out[kind] += int(a.size) * a.dtype.itemsize
        jax.tree_util.tree_map_with_path(add, self.caches)
        return out

    @property
    def nbytes(self) -> int:
        """Total bytes of the pooled cache pytree (the serve-memory
        number the dtype ladder reports)."""
        return sum(int(a.size) * a.dtype.itemsize
                   for a in jax.tree.leaves(self.caches))

    # -- allocation ---------------------------------------------------------
    def alloc(self):
        """Claim a free slot id, or None when every slot is in use."""
        if not self._free:
            return None
        slot = self._free.pop()
        self._used.add(slot)
        return slot

    def free(self, slot: int):
        """Return a slot to the pool.  Double-free is a scheduler bug and
        raises instead of silently corrupting the free list."""
        if slot not in self._used:
            raise ValueError(f"free of slot {slot} not in use")
        self._used.remove(slot)
        self._free.append(slot)

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_used(self) -> int:
        return len(self._used)

    # -- state movement -----------------------------------------------------
    def commit(self, slot: int, new_caches):
        """Scatter a finished batch-1 prefill cache into ``slot`` (every
        leaf's slot row, recurrent state and KV alike, so a reused slot
        keeps nothing of its previous occupant)."""
        self.caches = commit_slots(self.cfg, self.caches, new_caches,
                                   jnp.asarray([slot], jnp.int32))

    def update(self, caches):
        """Install the post-decode batched caches (all slots at once),
        re-narrowing floating leaves to ``state_dtype`` — decode steps
        hand back f32/compute-dtype state (they compute in f32 and the
        attention path preserves its cache dtype), and the pool must not
        silently widen after the first tick."""
        self.caches = narrow_state(caches, self.state_dtype)
