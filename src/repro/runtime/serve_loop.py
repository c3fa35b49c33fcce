"""Batched serving engine with continuous batching and multi-user adapters —
the inference half of FTaaS: one base model, K users' adapters applied
per-request inside one decode batch (multi-LoRA; the ``multi_lora`` Pallas
kernel's job on TPU).

Design: fixed decode slots. Each slot holds (request id, user id, position,
done). Admission drains up to ``admit_batch`` waiting requests per tick into
free slots and prefills them **as one padded batch** through
``model_lib.prefill`` (per-row user-id adapter routing via the multi_lora
kernel), scattering the resulting KV/state into the slot cache
(``model_lib.scatter_prefill_cache``). Every engine tick then decodes one
token for all live slots.

Lifecycle:  submit -> admit (batched prefill into slots) -> decode ticks ->
complete (slot freed, stats recorded).

Slot-mask invariant: every decode step carries a (slots,) ``live`` mask and
``model_lib.decode_step`` reverts cache writes of non-live rows, so neither
admission nor decoding on behalf of a subset of slots can touch another live
slot's KV (the old single-row prefill clobbered position 0 of every other
slot — fixed here and guarded by tests/test_serving.py).

The token-by-token single-row path is kept as a reference implementation
(``prefill_mode="reference"``) for the batched==reference equivalence tests.

Chunked prefill (``prefill_chunk=C``): instead of one monolithic prefill
forward at admission, each prompt is split into C-token chunks and exactly one
chunk round runs per engine tick, interleaved with the live decode batch
(Sarathi-style). A long prompt then stalls decode by at most one chunk of
model work per tick instead of a full prompt forward. Chunks run through
``model_lib.decode_step`` with ``c > 1`` tokens per row: non-recurrent rows go
as one width-C padded group (per-row ``lens`` gathers each row's last real
logit), recurrent (ssm/hybrid) rows are grouped by exact chunk width so no
padding ever touches conv/ssd state, which is carried across chunk boundaries
exactly. The first generated token is emitted straight from the final chunk's
logits (and, unchunked, from the prefill logits via ``prefill(lengths=)``) —
no decode tick is spent re-deriving it.

Paged KV (``kv_layout="paged"``, requires chunked prefill): the dense
(slots, max_len) slot cache is replaced by a shared block pool plus a per-slot
block table (``runtime.kv_pager.BlockPager``). Blocks are allocated on demand
as positions are written and freed at retirement, so KV HBM scales with
*tokens held*, not slots x horizon — ``max_len`` becomes a virtual horizon
that only sizes the block table. Admission reserves each request's worst-case
block count up front, so a mid-flight allocation can never run the pool dry.
gemma2-style local-window stacks keep a per-slot ring cache of
``local_window + C - 1`` positions instead of pool blocks.

Adapter banks come in two flavours: the dense device-resident stack
(``stack_user_adapters``; U bounded by HBM) and, with ``resident_slots=R``,
the tiered ``AdapterStore`` (runtime/adapter_store.py): every user lives in a
host-tier numpy bank and only an R-row LRU cache is device-resident. Admission
pins users and prefetches their residency; decode/prefill then route by
*resident row index* (``res_idx``), never by global user id, so adapter HBM
and kernel cost are bounded by R while tokens stay bit-identical to the
all-resident engine.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import gl
from repro.core import taps as taps_lib
from repro.models import model as model_lib
from repro.runtime.adapter_store import AdapterStore
from repro.runtime.kv_pager import BlockPager
from repro.telemetry import span
from repro.telemetry.metrics import NULL_METRIC, percentiles

Array = jax.Array


@dataclasses.dataclass
class Request:
    rid: int
    user: int
    prompt: np.ndarray          # (P,) int32
    max_new: int = 16
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    # "queued" -> "done" | "rejected:<reason>" (terminal without a slot)
    status: str = "queued"
    # lifecycle timestamps (perf_counter seconds), filled by the engine
    t_submit: float | None = None
    t_admit: float | None = None
    t_first: float | None = None
    t_done: float | None = None

    @property
    def ttft(self) -> float | None:
        """Time to first token, from submission."""
        if self.t_submit is None or self.t_first is None:
            return None
        return self.t_first - self.t_submit

    @property
    def latency(self) -> float | None:
        if self.t_submit is None or self.t_done is None:
            return None
        return self.t_done - self.t_submit


def stack_user_adapters(adapter_list: list[dict]) -> dict:
    """K per-user adapter pytrees {tap: {"A": (L?,d,r), "B": ...}} -> multi
    bank {tap: {"A": (L?,U,d,r), ...}} (user axis after any layer axis)."""
    if not adapter_list:
        raise ValueError("stack_user_adapters: need at least one per-user "
                         "adapter pytree, got an empty list")

    def _struct(a: dict) -> dict:
        return {tap: {n: tuple(np.shape(l)) for n, l in sorted(leaves.items())}
                for tap, leaves in a.items()}

    want = _struct(adapter_list[0])
    for u, a in enumerate(adapter_list[1:], start=1):
        got = _struct(a)
        if got != want:
            raise ValueError(
                f"stack_user_adapters: user {u} adapter structure {got} does "
                f"not match user 0 structure {want} (all users must share the "
                "same tap set and leaf shapes)")
    out: dict[str, Any] = {}
    for tap in adapter_list[0]:
        leaves = {}
        for name in adapter_list[0][tap]:
            stacked = jnp.stack([a[tap][name] for a in adapter_list], axis=0)
            if adapter_list[0][tap][name].ndim > 2:   # (L, d, r) -> (L, U, d, r)
                stacked = jnp.moveaxis(stacked, 0, 1)
            leaves[name] = stacked
        out[tap] = leaves
    return out


def quantize_bank(bank: dict) -> dict:
    """f32 multi-user bank -> int8-stored bank: every leaf ``name`` becomes
    ``name_q`` (int8) + ``name_scale`` (per-row f32). The serve path then
    dequantises on kernel tile load (kernels/multi_lora.multi_lora_q8) instead
    of ever holding a f32 copy of the bank — 4x less adapter HBM per user."""
    from repro.kernels import multi_lora as ml
    out: dict[str, Any] = {}
    for tap, leaves in bank.items():
        entry = {}
        for name, leaf in leaves.items():
            q, s = ml.quant_rows(leaf)
            entry[f"{name}_q"] = q
            entry[f"{name}_scale"] = s
        out[tap] = entry
    return out


def publish_banks(engine: "ServeEngine", channels) -> int:
    """Install every `OffloadChannel`'s bank that carries a validated version
    bump into the serving engine (the train -> serve hot-swap path). Channels
    that are quarantined or stale simply keep serving their last-good bank.

    With a tiered adapter store, a channel whose user the engine has never
    seen is *registered* into the host tier (new users join serving without a
    bank restack); without one, out-of-range users are skipped and counted in
    ``stats["bank_unknown_user"]`` instead of crashing the publish sweep.
    Returns the number of banks installed (registrations included)."""
    installed = 0
    for ch in channels:
        if engine.store is not None:
            if not engine.store.knows(ch.user):
                if engine.install_adapters(ch.user, ch.adapters, ch.version):
                    installed += 1
                continue
            if ch.version > engine.store.version(ch.user):
                if engine.install_adapters(ch.user, ch.adapters, ch.version):
                    installed += 1
            continue
        if engine.bank_versions is None:
            break
        if not 0 <= ch.user < engine.n_users:
            engine.stats["bank_unknown_user"] += 1
            continue
        if ch.version > int(engine.bank_versions[ch.user]):
            if engine.install_adapters(ch.user, ch.adapters, ch.version):
                installed += 1
    return installed


def _bucket(n: int, floor: int = 8) -> int:
    """Round up to a power of two (>= floor) to bound jit recompilations of the
    prefill step across varying admitted-batch shapes."""
    b = floor
    while b < n:
        b *= 2
    return b


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params: dict, *, slots: int = 8,
                 max_len: int = 512, user_adapters: list[dict] | None = None,
                 taps: str = "qv", scale: float = 1.0,
                 prefill_mode: str = "batched", admit_batch: int | None = None,
                 bank_store: str = "f32", decode_burst: int = 1,
                 resident_slots: int | None = None,
                 cluster_threshold: float | None = None,
                 cluster_mode: str = "shared",
                 prefill_chunk: int | None = None,
                 kv_layout: str = "dense", kv_block: int = 16,
                 kv_blocks: int | None = None,
                 max_prompt: int | None = None,
                 telemetry=None):
        assert prefill_mode in ("batched", "reference"), prefill_mode
        assert bank_store in ("f32", "int8"), bank_store
        assert kv_layout in ("dense", "paged"), kv_layout
        if prefill_chunk is not None:
            assert prefill_chunk >= 1, prefill_chunk
            assert prefill_mode == "batched", (
                "chunked prefill requires prefill_mode='batched' (the "
                "reference mode exists to oracle the unchunked path)")
        if kv_layout == "paged":
            assert prefill_chunk is not None, (
                "kv_layout='paged' requires prefill_chunk: the monolithic "
                "prefill scatters a dense cache (scatter_prefill_cache), "
                "only the chunked path writes through the block table")
        # Telemetry is strictly observational: it only reads host-side values
        # after dispatches complete, so generated tokens are bit-identical
        # telemetry-on vs. off (guarded by tests/test_telemetry.py). The
        # disabled path is `self.tm is None` checks plus NULL_METRIC no-ops.
        self.tm = telemetry if telemetry else None
        self._tracer = self.tm.tracer if self.tm else None
        _reg = self.tm.registry if self.tm else None
        _hist = (_reg.histogram if _reg is not None
                 else (lambda name: NULL_METRIC))
        self._h_ttft = _hist("serve.ttft_s")
        self._h_latency = _hist("serve.latency_s")
        self._h_decode_tick = _hist("serve.decode_tick_s")
        self._h_prefill_chunk = _hist("serve.prefill_chunk_s")
        self._h_prefill_call = _hist("serve.prefill_call_s")
        if self.tm:
            self.tm.name_thread(0, "serve")
        # always-on bounded duration samples so throughput() reports tail
        # percentiles (satellite 1) even without a Telemetry attached
        self._decode_tick_s: collections.deque = collections.deque(maxlen=4096)
        self._prefill_s: collections.deque = collections.deque(maxlen=4096)
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.prefill_mode = prefill_mode
        self.prefill_chunk = prefill_chunk
        self.kv_layout = kv_layout
        self.kv_block = kv_block
        # a prompt occupies [0, P) and one decode position must remain below
        # the horizon, so max_prompt can never exceed max_len - 1
        self.max_prompt = (int(max_prompt) if max_prompt is not None
                           else max_len - 1)
        assert 1 <= self.max_prompt <= max_len - 1, self.max_prompt
        self.admit_batch = admit_batch if admit_batch is not None else slots
        self.bank_store = bank_store
        # Burst decoding: fuse up to ``decode_burst`` decode ticks into one
        # jitted lax.scan, amortising per-dispatch overhead. Bursts only run
        # when no live slot could complete mid-burst, so emitted tokens are
        # bit-identical to decode_burst=1 (guarded by tests/test_serving.py).
        self.decode_burst = max(1, int(decode_burst))
        self.queue: list[Request] = []
        self.finished: list[Request] = []
        self.active: list[Request | None] = [None] * slots
        self.positions = np.zeros(slots, np.int32)
        self.users = np.zeros(slots, np.int32)
        self.pager: BlockPager | None = None
        ring_len = None
        if kv_layout == "paged":
            n_blocks = (kv_blocks if kv_blocks is not None
                        else slots * (-(-max_len // kv_block)))
            self.pager = BlockPager(n_blocks, kv_block, slots, max_len,
                                    telemetry=self.tm)
            kv_blocks = n_blocks
            if model_lib.layer_plan(cfg)[0] == "pairs":
                # local-window ring: must hold the window plus a full chunk's
                # in-flight writes (see models/attention.attention_decode)
                ring_len = (cfg.local_window or max_len) + prefill_chunk - 1
        self.cache = model_lib.init_cache(cfg, slots, max_len,
                                          kv_layout=kv_layout,
                                          kv_blocks=kv_blocks,
                                          kv_block=kv_block, ring_len=ring_len)
        self.spec = None
        self.bank = None
        self.store: AdapterStore | None = None
        self.res_idx = np.zeros(slots, np.int32)   # per-slot resident row
        self.n_users = 0
        self.bank_versions: np.ndarray | None = None
        if user_adapters:
            tap_names = gl.select_taps(cfg, taps)
            self.spec = taps_lib.make_spec(family="multi_lowrank",
                                           taps=tap_names, scale=scale)
            self.n_users = len(user_adapters)
            if resident_slots is not None:
                # tiered store: host tier holds every user, the device bank is
                # a fixed-R LRU cache — user count decouples from HBM.
                self.store = AdapterStore.from_users(
                    user_adapters, resident=resident_slots, store=bank_store,
                    telemetry=self.tm)
                if cluster_threshold is not None:
                    self.store.build_clusters(cluster_threshold,
                                              mode=cluster_mode)
            else:
                self.bank = stack_user_adapters(user_adapters)
                if bank_store == "int8":
                    self.bank = quantize_bank(self.bank)
                self.bank_versions = np.zeros(self.n_users, np.int64)
        elif resident_slots is not None:
            raise ValueError("resident_slots requires user_adapters (the "
                             "store template comes from the first user)")
        self._recurrent = model_lib.has_recurrent_state(cfg)
        self._decode = jax.jit(self._decode_fn)
        self._decode_n = jax.jit(self._decode_burst_fn, static_argnames=("n",))
        self._prefill = jax.jit(self._prefill_fn)
        self._chunk = jax.jit(self._chunk_fn)
        self.stats = {"ticks": 0, "tokens": 0, "decode_tokens": 0,
                      "completed": 0, "admitted": 0,
                      "prefill_calls": 0, "prefill_tokens": 0,
                      "prefill_chunks": 0, "chunk_rounds": 0,
                      "decode_time": 0.0, "prefill_time": 0.0,
                      "rejected": 0, "bank_installs": 0, "bank_rejected": 0,
                      "bank_unknown_user": 0,
                      "kv_blocks_in_use": 0, "kv_blocks_peak": 0,
                      "kv_allocs": 0, "kv_frees": 0, "kv_reserve_failures": 0,
                      "store_hits": 0, "store_misses": 0, "store_evictions": 0,
                      "store_hit_rate": 0.0, "store_pinned": 0,
                      "store_resident_bytes": 0, "store_fetch_time": 0.0}

    # -- telemetry ---------------------------------------------------------
    def _record(self, scope: str, key, kind: str, **fields) -> None:
        if self.tm is not None:
            self.tm.record(scope, key, kind, **fields)

    def telemetry_snapshot(self) -> dict:
        """Sync the legacy stat dicts, absorb them into the metric registry
        under ``serve.*`` / ``store.*`` / ``pager.*`` and return the registry
        snapshot. Empty dict when telemetry is disabled — ``engine.stats``
        stays the always-on authority."""
        if self.tm is None:
            return {}
        self._sync_store_stats()
        self._sync_pager_stats()
        reg = self.tm.registry
        # store_*/kv_* keys are mirrors of the store/pager dicts; absorb the
        # originals under their own namespaces instead of duplicating them
        reg.absorb("serve", {k: v for k, v in self.stats.items()
                             if not k.startswith(("store_", "kv_"))})
        if self.store is not None:
            reg.absorb("store", self.store.metrics())
        if self.pager is not None:
            reg.absorb("pager", self.pager.stats)
        return reg.snapshot()

    # -- jitted core -----------------------------------------------------
    # The bank is a jit *argument*, never a closure: a closed-over bank would
    # be baked into the compiled decode as a trace-time constant, silently
    # ignoring every later `install_adapters` hot-swap (shapes are stable
    # across swaps, so passing it as an input costs no recompilation).
    def _cola_vars(self, bank, users: Array) -> dict | None:
        if bank is None:
            return None
        vars_ = {}
        for tap, leaves in bank.items():
            entry = dict(leaves)
            a = leaves.get("A", leaves.get("A_q"))   # int8 banks carry A_q
            if a.ndim == 4:   # stacked (L, U, d, r): idx must carry the layer
                entry["idx"] = jnp.broadcast_to(users, (a.shape[0],) + users.shape)
            else:
                entry["idx"] = users
            vars_[tap] = entry
        return {"adapters": vars_}

    def _decode_fn(self, params, bank, cache, table, tokens, positions, users,
                   live):
        batch = {"tokens": tokens, "positions": positions}
        logits, cache = model_lib.decode_step(
            self.cfg, params, batch, cache, self.spec,
            self._cola_vars(bank, users), live=live, block_table=table)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tok, cache

    def _decode_burst_fn(self, params, bank, cache, table, tokens, positions,
                         users, live, *, n: int):
        """``n`` chained decode ticks in one jitted lax.scan: each step feeds
        its argmax token back as the next step's input and advances live rows'
        positions. Returns the (n, slots) token trace plus the final cache.
        Dead rows keep their input token and position, matching what the
        host-side loop would have passed on every individual tick."""
        def body(carry, _):
            toks, pos, cache = carry
            batch = {"tokens": toks, "positions": pos}
            logits, cache = model_lib.decode_step(
                self.cfg, params, batch, cache, self.spec,
                self._cola_vars(bank, users), live=live, block_table=table)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            toks = jnp.where(live, nxt, toks[:, 0])[:, None]
            pos = pos + live.astype(pos.dtype)
            return (toks, pos, cache), nxt
        (_, _, cache), trace = jax.lax.scan(
            body, (tokens, positions, cache), None, length=n)
        return trace, cache

    def _prefill_fn(self, params, bank, cache, tokens, users, slot_ids,
                    lengths):
        """Run a padded (J, P) prompt batch through full-sequence prefill,
        scatter each row's KV/state into its slot and return each row's first
        generated token (argmax of the logits at its true last prompt
        position, gathered by ``lengths``). Padding rows carry an out-of-range
        slot id and are dropped by the scatter."""
        logits, pre = model_lib.prefill(self.cfg, params, {"tokens": tokens},
                                        self.spec, self._cola_vars(bank, users),
                                        lengths=lengths)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tok, model_lib.scatter_prefill_cache(cache, pre, slot_ids)

    def _chunk_fn(self, params, bank, cache, table, tokens, positions, users,
                  live, lens):
        """One prefill chunk round: a (slots, width) token batch through the
        multi-token decode step. ``lens[i]`` is row i's real chunk length
        (<= width; the rest is padding whose cache writes are masked/dropped);
        the returned token is each row's argmax at its last real position —
        meaningful only for rows whose prompt just completed."""
        batch = {"tokens": tokens, "positions": positions}
        logits, cache = model_lib.decode_step(
            self.cfg, params, batch, cache, self.spec,
            self._cola_vars(bank, users), live=live, block_table=table)
        rows = jnp.arange(tokens.shape[0])
        last = logits[rows, jnp.clip(lens - 1, 0)]
        return jnp.argmax(last, axis=-1).astype(jnp.int32), cache

    # -- dispatch routing --------------------------------------------------
    # With a tiered store the jitted decode/prefill receive the R-row resident
    # bank and *resident row indices*; without one, the dense U-user bank and
    # global user ids. Shapes are stable either way, so jit caches one variant.
    def _dispatch_bank(self):
        return self.store.bank if self.store is not None else self.bank

    def _dispatch_idx(self) -> np.ndarray:
        return self.res_idx if self.store is not None else self.users

    # -- engine ------------------------------------------------------------
    def _validate(self, req: Request) -> str | None:
        if len(req.prompt) == 0:
            return "empty prompt"
        # a prompt occupies positions [0, P); at least one decode tick must fit
        # below the cache horizon, so max_prompt is capped at max_len - 1
        if len(req.prompt) > self.max_prompt:
            return (f"prompt length {len(req.prompt)} > max_prompt "
                    f"{self.max_prompt} (horizon max_len={self.max_len})")
        if req.max_new <= 0:
            return f"max_new must be positive, got {req.max_new}"
        if self.store is not None:
            if not self.store.knows(req.user):
                return (f"unknown user {req.user} (store has "
                        f"{len(self.store.users())})")
        elif self.bank is not None and not 0 <= req.user < self.n_users:
            return f"unknown user {req.user} (bank has {self.n_users})"
        return None

    def submit(self, req: Request) -> None:
        """Queue a request — or reject it with a terminal status (bad requests
        must never crash a tick or occupy a slot)."""
        req.t_submit = time.perf_counter()
        reason = self._validate(req)
        if reason is not None:
            req.status = f"rejected: {reason}"
            req.done = True
            req.t_done = req.t_submit
            self.stats["rejected"] += 1
            self.finished.append(req)
            return
        self.queue.append(req)

    # -- adapter bank lifecycle ---------------------------------------------
    def install_adapters(self, user: int, adapters: dict, version: int) -> bool:
        with span("serve.bank_install", self._tracer, user=user,
                  version=version):
            ok = self._install_adapters(user, adapters, version)
        self._record("user", user, "bank_install", version=version, ok=ok)
        return ok

    def _install_adapters(self, user: int, adapters: dict, version: int) -> bool:
        """Hot-swap one user's adapters into the serving bank.

        Accepts only *validated version bumps*: the version must exceed the
        user's installed version and every leaf must be finite — anything else
        is rejected and the user keeps serving their last-good adapters
        (graceful degradation for quarantined / stale users). Returns whether
        the bank was installed.

        With a tiered store, the commit lands in the host tier (registering
        brand-new users); a clustered user is split off their shared adapter
        (copy-on-write) without perturbing other members, and a live user's
        resident row is refreshed in place.
        """
        if self.store is not None:
            return self._install_store(user, adapters, version)
        if self.bank is None or not 0 <= user < self.n_users:
            self.stats["bank_rejected"] += 1
            return False
        if version <= int(self.bank_versions[user]):
            self.stats["bank_rejected"] += 1   # stale or replayed update
            return False
        leaves = jax.tree.leaves(adapters)
        if not all(bool(jnp.isfinite(l).all()) for l in leaves):
            self.stats["bank_rejected"] += 1   # unvalidated/poisoned bank
            return False
        if set(adapters) != set(self.bank):
            self.stats["bank_rejected"] += 1   # wrong tap set for this bank
            return False
        # a bank fitted on the offload device joins the serving bank on the
        # serving device (else the updated bank, and decode, would move)
        adapters = jax.device_put(
            adapters, jax.tree.leaves(self.bank)[0].sharding)
        new_bank = {}
        for tap, entry in self.bank.items():
            new_entry = dict(entry)
            for name, leaf in adapters[tap].items():
                user_slot = ((slice(None), user) if leaf.ndim > 2 else user)
                if f"{name}_q" in entry:
                    # int8-stored bank: quantise the incoming f32 leaf and
                    # swap in both the codes and the per-row scales.
                    from repro.kernels import multi_lora as ml
                    q, s = ml.quant_rows(jnp.asarray(leaf, jnp.float32))
                    stacked_q = entry[f"{name}_q"]
                    if q.shape != stacked_q[user_slot].shape:
                        self.stats["bank_rejected"] += 1
                        return False
                    new_entry[f"{name}_q"] = stacked_q.at[user_slot].set(q)
                    new_entry[f"{name}_scale"] = (
                        entry[f"{name}_scale"].at[user_slot].set(s))
                    continue
                stacked = entry[name]
                if leaf.shape != stacked[user_slot].shape:
                    self.stats["bank_rejected"] += 1
                    return False
                new_entry[name] = stacked.at[user_slot].set(leaf)
            new_bank[tap] = new_entry
        self.bank = new_bank
        self.bank_versions[user] = version
        self.stats["bank_installs"] += 1
        return True

    def _install_store(self, user: int, adapters: dict, version: int) -> bool:
        """Tiered-store install: host-tier commit + in-place resident-row
        refresh. Unknown users are registered (they become servable without
        any restack); known users need a version bump and finite leaves."""
        st = self.store
        leaves = jax.tree.leaves(adapters)
        if not all(bool(jnp.isfinite(l).all()) for l in leaves):
            self.stats["bank_rejected"] += 1   # unvalidated/poisoned bank
            return False
        try:
            if not st.knows(user):
                st.register(user, adapters, version=version)
            else:
                if version <= st.version(user):
                    self.stats["bank_rejected"] += 1   # stale or replayed
                    return False
                st.install(user, adapters, version)
        except ValueError:   # wrong tap set / leaf shapes for this store
            self.stats["bank_rejected"] += 1
            return False
        self.stats["bank_installs"] += 1
        # A COW split moves the user onto a fresh host entry while their live
        # slots still point at the old (cluster) row: re-resolve residency now
        # if a row is free/evictable, else their in-flight requests finish on
        # the old adapters and residency refreshes at the next admission.
        live = [i for i, r in enumerate(self.active)
                if r is not None and r.user == user]
        if live:
            try:
                row = st.ensure_resident([user])[0]
            except RuntimeError:
                pass
            else:
                for i in live:
                    self.res_idx[i] = row
        return True

    def _reserve_len(self, req: Request) -> int:
        """Worst-case positions ``req`` can ever write on its slot: the
        chunk-padded prompt (non-recurrent chunk rounds write width-C tails)
        or the decode horizon, whichever is larger, clipped to max_len.
        Reserving this at admission means mid-flight ``ensure`` never fails."""
        P = len(req.prompt)
        C = self.prefill_chunk or P
        padded = -(-P // C) * C
        return min(self.max_len, max(padded, P + req.max_new))

    def _table(self):
        return jnp.asarray(self.pager.table) if self.pager is not None else None

    def _admit(self) -> list[int]:
        """Admit up to ``admit_batch`` waiting requests into free slots
        (reserving KV blocks and pinning adapter rows); returns their slots.
        The chunked path prefills nothing here — chunk rounds in subsequent
        ticks stream the prompts in; the unchunked paths prefill the admitted
        rows next (``_prefill_admitted``)."""
        admitted: list[int] = []
        now = time.perf_counter()
        for i in range(self.slots):
            if len(admitted) >= self.admit_batch or not self.queue:
                break
            if self.active[i] is not None:
                continue
            req = self.queue[0]
            if (self.pager is not None
                    and not self.pager.reserve(i, self._reserve_len(req))):
                # pool pressure: admission waits (FIFO) until retirements
                # return enough blocks to back this request's worst case.
                break
            if self.store is not None and not self.store.acquire(req.user):
                # every resident row is pinned by a distinct live user:
                # admission waits (FIFO) until a request completes.
                if self.pager is not None:
                    self.pager.release(i)   # roll back the reservation
                break
            self.queue.pop(0)
            req.t_admit = now
            req._consumed = 0
            self.active[i] = req
            self.users[i] = req.user
            self.positions[i] = 0
            admitted.append(i)
        if not admitted:
            return admitted
        if self.store is not None:
            # prefetch-on-admission: residency is ensured (host -> device
            # fetch on miss) before any prefill/decode touches these slots.
            res_rows = self.store.ensure_resident(
                [self.active[i].user for i in admitted])
            for k, i in enumerate(admitted):
                self.res_idx[i] = res_rows[k]
        self.stats["admitted"] += len(admitted)
        for i in admitted:
            r = self.active[i]
            self._record("slot", i, "admit", rid=r.rid, user=r.user,
                         prompt_len=len(r.prompt))
        return admitted

    def _run(self, program, args: tuple, **static) -> np.ndarray:
        """Dispatch one run of a jitted ``program`` (whose arguments the
        caller's ``serve.prep`` phase built and uploaded) and wait for the
        tokens it returns: the ``serve.dispatch`` and ``serve.sync`` phases.
        The cache the program returns replaces the engine's."""
        with span("serve.dispatch", self._tracer):
            out, self.cache = program(*args, **static)
        with span("serve.sync", self._tracer):
            return np.asarray(out)

    def _prefill_admitted(self, admitted: list[int]) -> None:
        """Unchunked prefill of the rows just admitted. The batched path pads
        them to one (J, P) batch and runs a single prefill forward
        (recurrent rows: one forward per row); the reference path feeds
        tokens one by one through the (live-masked) decode step. All emit
        each request's first generated token from the prompt's own logits,
        never a decode tick."""
        tr = self._tracer
        rows = [(i, np.asarray(self.active[i].prompt, np.int32))
                for i in admitted]
        t0 = time.perf_counter()

        def book(now: float) -> None:   # the admission's last emit
            dt = now - t0
            self.stats["prefill_time"] += dt
            self._prefill_s.append(dt)
            self._h_prefill_call.observe(dt)
            self.stats["prefill_calls"] += 1
            self.stats["prefill_tokens"] += sum(len(f) for _, f in rows)
            for i, _ in rows:
                if self.active[i] is not None:
                    self._maybe_finish(i, now)
            self._sync_store_stats()
            self._sync_pager_stats()

        if self.prefill_mode == "batched" and not self._recurrent:
            with span("serve.prep", tr):
                args = self._prefill_args(rows)
            nxt = self._run(self._prefill, args)
            with span("serve.emit", tr):
                now = time.perf_counter()
                for r, (i, _) in enumerate(rows):
                    self._first_token(i, int(nxt[r]), now)
                book(now)
            return
        for k, (i, feed) in enumerate(rows):
            if self.prefill_mode == "reference":
                for t, tok in enumerate(feed):
                    nxt = self._feed(i, int(tok), t)
            else:
                # Recurrent (ssm/conv) state folds in every input token, so
                # a right-padded batch would pollute shorter rows' state:
                # prefill each row at its exact length (still one forward
                # per prompt instead of one decode step per token).
                with span("serve.prep", tr):
                    args = (self.params, self._dispatch_bank(), self.cache,
                            jnp.asarray(feed[None, :]),
                            jnp.asarray(self._dispatch_idx()[i:i + 1]),
                            jnp.asarray(np.array([i], np.int32)),
                            jnp.asarray(np.array([len(feed)], np.int32)))
                nxt = int(self._run(self._prefill, args)[0])
            with span("serve.emit", tr):
                now = time.perf_counter()
                self._first_token(i, nxt, now)
                if k == len(rows) - 1:
                    book(now)

    def _prefill_args(self, rows: list[tuple[int, np.ndarray]]) -> tuple:
        # attention KV: pad-token garbage beyond a row's true length is safe
        # (decode overwrites position p before attending; causality hides > p),
        # so bucket shapes to bound jit recompilation. The bucket never
        # exceeds max_len, which bounds the cache's sequence axis.
        pmax = min(_bucket(max(len(feed) for _, feed in rows)), self.max_len)
        j = _bucket(len(rows), floor=1)
        toks = np.zeros((j, pmax), np.int32)
        users = np.zeros((j,), np.int32)
        lengths = np.ones((j,), np.int32)
        # padding rows point at slot id == slots (out of range -> dropped)
        slot_ids = np.full((j,), self.slots, np.int32)
        for r, (i, feed) in enumerate(rows):
            toks[r, :len(feed)] = feed
            users[r] = self._dispatch_idx()[i]
            slot_ids[r] = i
            lengths[r] = len(feed)
        return (self.params, self._dispatch_bank(), self.cache,
                jnp.asarray(toks), jnp.asarray(users), jnp.asarray(slot_ids),
                jnp.asarray(lengths))

    def _feed(self, slot: int, token: int, pos: int) -> int:
        """Reference single-row prefill step: decode one prompt token into one
        slot's cache and return the argmax token (the last feed's return is
        the request's first generated token). The live mask confines the cache
        write to ``slot`` (the unmasked version corrupted position 0 of every
        other live slot)."""
        with span("serve.prep", self._tracer):
            toks = np.zeros((self.slots, 1), np.int32)
            toks[slot, 0] = token
            positions = np.zeros((self.slots,), np.int32)
            positions[slot] = pos
            live = np.zeros((self.slots,), bool)
            live[slot] = True
            args = (self.params, self._dispatch_bank(), self.cache, None,
                    jnp.asarray(toks), jnp.asarray(positions),
                    jnp.asarray(self._dispatch_idx()), jnp.asarray(live))
        return int(self._run(self._decode, args)[slot])

    def _first_token(self, i: int, tok: int, now: float) -> None:
        """Record a request's first generated token (emitted from its prompt's
        own logits at prefill/chunk completion) and arm the slot for decode:
        the next decode tick feeds this token at position P."""
        req = self.active[i]
        req.t_first = now
        req.out.append(tok)
        req._last = tok
        req._consumed = len(req.prompt)   # prompt fully in cache: decode-live
        self.positions[i] = len(req.prompt)
        self.stats["tokens"] += 1
        self._h_ttft.observe(now - req.t_submit)
        self._record("slot", i, "first_token", rid=req.rid, user=req.user,
                     ttft=now - req.t_submit)

    def _maybe_finish(self, i: int, now: float) -> None:
        req = self.active[i]
        if (len(req.out) >= req.max_new
                or self.positions[i] >= self.max_len - 1):
            self._retire(i, now)

    def _retire(self, i: int, now: float) -> None:
        req = self.active[i]
        req.done = True
        req.status = "done"
        req.t_done = now
        self.stats["completed"] += 1
        if req.latency is not None:
            self._h_latency.observe(req.latency)
        self._record("slot", i, "retire", rid=req.rid, user=req.user,
                     new_tokens=len(req.out))
        self.finished.append(req)
        self.active[i] = None
        self.positions[i] = 0
        if self.pager is not None:
            self.pager.release(i)
        if self.store is not None:
            self.store.release(req.user)

    def _chunk_round(self) -> list[int]:
        """Advance every mid-prefill slot by one chunk (Sarathi interleave:
        exactly one round per tick, so a long prompt costs each decode tick at
        most one chunk of extra model work). Non-recurrent rows run as one
        width-C padded group; recurrent rows are grouped by exact chunk width
        so padding never touches conv/ssd state. Returns the slots that were
        mid-prefill at entry."""
        pend = [i for i, r in enumerate(self.active)
                if r is not None and r._consumed < len(r.prompt)]
        if not pend:
            return pend
        C = self.prefill_chunk
        t0 = time.perf_counter()
        if self._recurrent:
            groups: dict[int, list[int]] = {}
            for i in pend:
                req = self.active[i]
                groups.setdefault(min(C, len(req.prompt) - req._consumed),
                                  []).append(i)
            todo = sorted(groups.items())
        else:
            todo = [(C, pend)]
        tr = self._tracer
        for k, (width, idx_list) in enumerate(todo):
            with span("serve.prep", tr):
                toks = np.zeros((self.slots, width), np.int32)
                lens = np.ones((self.slots,), np.int32)
                live = np.zeros((self.slots,), bool)
                pos = np.zeros((self.slots,), np.int32)
                for i in idx_list:
                    req = self.active[i]
                    c = min(width, len(req.prompt) - req._consumed)
                    toks[i, :c] = req.prompt[req._consumed:req._consumed + c]
                    lens[i] = c
                    live[i] = True
                    pos[i] = req._consumed
                    if self.pager is not None:
                        ok = self.pager.ensure(
                            i, min(req._consumed + width - 1,
                                   self.max_len - 1))
                        assert ok, ("admission reservation must cover the "
                                    "prompt")
                args = (self.params, self._dispatch_bank(), self.cache,
                        self._table(), jnp.asarray(toks), jnp.asarray(pos),
                        jnp.asarray(self._dispatch_idx()), jnp.asarray(live),
                        jnp.asarray(lens))
            nxt = self._run(self._chunk, args)
            with span("serve.emit", tr):
                now = time.perf_counter()
                for i in idx_list:
                    req = self.active[i]
                    c = min(width, len(req.prompt) - req._consumed)
                    req._consumed += c
                    self.stats["prefill_tokens"] += c
                    if req._consumed >= len(req.prompt):
                        self._first_token(i, int(nxt[i]), now)
                        self._maybe_finish(i, now)
                self.stats["prefill_chunks"] += len(idx_list)
                if k == len(todo) - 1:   # the round's last emit
                    self.stats["chunk_rounds"] += 1
                    dt = time.perf_counter() - t0
                    self.stats["prefill_time"] += dt
                    self._prefill_s.append(dt)
                    self._h_prefill_chunk.observe(dt)
                    self._sync_store_stats()
                    self._sync_pager_stats()
        return pend

    def _burst_len(self, live_idx: list[int]) -> int:
        """Largest safe burst: no live slot may complete (or first-token) inside
        a burst, so the host loop only ever observes burst boundaries. Burst
        sizes are powers of two to bound jit recompilations to log2 variants."""
        if self.decode_burst <= 1:
            return 1
        bound = self.decode_burst
        for i in live_idx:
            req = self.active[i]
            remaining = min(req.max_new - len(req.out),
                            self.max_len - 1 - int(self.positions[i]))
            bound = min(bound, remaining)
        if bound <= 1:
            return 1
        n = 1
        while n * 2 <= bound:
            n *= 2
        return n

    def tick(self) -> int:
        """One engine iteration: admit, advance mid-prefill slots by one chunk
        (chunked mode), then decode one token for every slot whose prompt is
        fully in cache (or a burst when ``decode_burst`` allows; bursts are
        capped to 1 while any slot is prefilling so the chunk interleave — and
        with it decode latency — stays per-tick flat)."""
        with span("serve.tick", self._tracer, tick=self.stats["ticks"]):
            return self._tick_inner()

    def _tick_inner(self) -> int:
        # Every piece of host work runs inside one phase span (admit, and
        # per program run prep / dispatch / sync / emit, see ``_run``);
        # only the choice of what runs next lies between them.
        tr = self._tracer
        if self.queue:
            with span("serve.admit", tr, queued=len(self.queue)):
                admitted = self._admit()
            if admitted and self.prefill_chunk is None:
                with span("serve.prefill", tr, rows=len(admitted)):
                    self._prefill_admitted(admitted)
        prefilling: list[int] = []
        if self.prefill_chunk is not None and any(
                r is not None and r._consumed < len(r.prompt)
                for r in self.active):
            with span("serve.prefill_chunk", tr):
                prefilling = self._chunk_round()
        live_idx = [i for i, r in enumerate(self.active)
                    if r is not None and r._consumed >= len(r.prompt)]
        if not live_idx:
            if prefilling:
                self.stats["ticks"] += 1
            else:   # no program ran: book what admission changed
                with span("serve.emit", tr):
                    self._sync_store_stats()
                    self._sync_pager_stats()
            return 0
        n = 1 if prefilling else self._burst_len(live_idx)
        with span("serve.decode", tr, live=len(live_idx), burst=n):
            return self._decode_round(live_idx, n)

    def _decode_round(self, live_idx: list[int], n: int) -> int:
        """Decode ``n`` tokens (one, or a burst) for every live slot."""
        tr = self._tracer
        with span("serve.prep", tr):
            toks = np.zeros((self.slots, 1), np.int32)
            live = np.zeros((self.slots,), bool)
            for i in live_idx:
                toks[i, 0] = self.active[i]._last
                live[i] = True
            if self.pager is not None:
                for i in live_idx:
                    ok = self.pager.ensure(
                        i, min(int(self.positions[i]) + n - 1,
                               self.max_len - 1))
                    assert ok, "admission reservation must cover the horizon"
            args = (self.params, self._dispatch_bank(), self.cache,
                    self._table(), jnp.asarray(toks),
                    jnp.asarray(self.positions),
                    jnp.asarray(self._dispatch_idx()), jnp.asarray(live))
            t0 = time.perf_counter()
        if n <= 1:
            trace = self._run(self._decode, args)[None]       # (1, slots)
        else:
            trace = self._run(self._decode_n, args, n=n)      # (n, slots)
        with span("serve.emit", tr):
            now = time.perf_counter()
            self.stats["decode_time"] += now - t0
            # one sample per tick decoded: a burst's dispatch wall is split
            # evenly so percentiles stay comparable across decode_burst
            self._decode_tick_s.append((now - t0) / trace.shape[0])
            self._h_decode_tick.observe((now - t0) / trace.shape[0])
            for step in range(trace.shape[0]):
                for i in live_idx:
                    req = self.active[i]
                    tok = int(trace[step, i])
                    req.out.append(tok)
                    req._last = tok
                    self.positions[i] += 1
            for i in live_idx:
                self._maybe_finish(i, now)
            self.stats["ticks"] += trace.shape[0]
            self.stats["tokens"] += trace.shape[0] * len(live_idx)
            self.stats["decode_tokens"] += trace.shape[0] * len(live_idx)
            self._sync_store_stats()
            self._sync_pager_stats()
        return trace.shape[0] * len(live_idx)

    def run_until_idle(self, max_ticks: int = 10_000) -> None:
        for _ in range(max_ticks):
            if not self.queue and all(r is None for r in self.active):
                break
            self.tick()

    # -- stats -------------------------------------------------------------
    def _sync_store_stats(self) -> None:
        """Mirror the adapter store's counters/gauges into ``engine.stats``."""
        if self.store is None:
            return
        m = self.store.metrics()
        self.stats["store_hits"] = m["hits"]
        self.stats["store_misses"] = m["misses"]
        self.stats["store_evictions"] = m["evictions"]
        self.stats["store_hit_rate"] = m["hit_rate"]
        self.stats["store_pinned"] = m["pinned"]
        self.stats["store_resident_bytes"] = m["resident_bytes"]
        self.stats["store_fetch_time"] = m["fetch_time"]

    def _sync_pager_stats(self) -> None:
        """Mirror the KV block pool's counters/gauges into ``engine.stats``."""
        if self.pager is None:
            return
        p = self.pager.stats
        self.stats["kv_blocks_in_use"] = p["in_use"]
        self.stats["kv_blocks_peak"] = p["peak_in_use"]
        self.stats["kv_allocs"] = p["allocs"]
        self.stats["kv_frees"] = p["frees"]
        self.stats["kv_reserve_failures"] = p["reserve_failures"]

    def kv_cache_bytes(self) -> int:
        """Decode-cache bytes attributable to current load. Dense: every leaf
        in full (the slot cache is the footprint, occupied or not). Paged:
        pool leaves are charged per *used* block — the quantity that scales
        with tokens held and that capacity planning sizes the pool by — plus
        the non-pool leaves (rings, recurrent state, block table) in full."""
        total = pool = 0
        for leaf in jax.tree.leaves(self.cache):
            nbytes = leaf.size * leaf.dtype.itemsize
            total += nbytes
            if (self.pager is not None and leaf.ndim == 5
                    and leaf.shape[1] == self.pager.n_blocks
                    and leaf.shape[2] == self.pager.block_size):
                pool += nbytes
        if self.pager is None:
            return total
        per_block = pool // max(self.pager.n_blocks, 1)
        table_bytes = self.pager.table.size * self.pager.table.itemsize
        return ((total - pool) + per_block * self.pager.blocks_in_use()
                + table_bytes)

    def request_stats(self) -> list[dict]:
        """Per-completed-request latency metrics (seconds)."""
        return [{"rid": r.rid, "user": r.user, "prompt_len": len(r.prompt),
                 "new_tokens": len(r.out), "ttft": r.ttft,
                 "latency": r.latency} for r in self.finished]

    def throughput(self) -> dict:
        """Aggregate engine throughput; decode tokens/sec excludes prefill.

        Tail latency rides along: ``ttft`` / ``latency`` summarise completed
        requests, ``decode_tick`` / ``prefill`` the per-dispatch duration
        rings (each is None or {count, mean, max, p50, p95, p99} seconds —
        means hide stalls, so report the percentiles, not ``mean_ttft``)."""
        dt = self.stats["decode_time"]
        pt = self.stats["prefill_time"]
        reqs = self.request_stats()
        ttfts = [r["ttft"] for r in reqs if r["ttft"] is not None]
        lats = [r["latency"] for r in reqs if r["latency"] is not None]
        self._sync_store_stats()
        self._sync_pager_stats()
        out = {
            "decode_tok_per_s": (self.stats["decode_tokens"] / dt
                                 if dt else 0.0),
            "prefill_tok_per_s": (self.stats["prefill_tokens"] / pt
                                  if pt else 0.0),
            "mean_ttft": float(np.mean(ttfts)) if ttfts else None,
            "ttft": percentiles(ttfts),
            "latency": percentiles(lats),
            "decode_tick": percentiles(self._decode_tick_s),
            "prefill": percentiles(self._prefill_s),
            "completed": self.stats["completed"],
        }
        if self.store is not None:
            out["store"] = self.store.metrics()
        if self.pager is not None:
            out["kv_blocks_in_use"] = self.pager.blocks_in_use()
            out["kv_blocks_peak"] = self.pager.stats["peak_in_use"]
        return out
