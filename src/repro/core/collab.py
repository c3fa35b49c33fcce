"""K-user collaboration (paper §3.2 FTaaS, Table 4).

Setups (paper Table 4):
- "joint":  one shared adapter bank trained on all users' data.
- "alone":  each user trains their own bank on their own rows (no merging
            during training); merging the K banks only at inference degrades —
            the paper's observation, reproduced in benchmarks/collaboration.py.
- "collab": all K banks merged into the base weights during training; each
            user's rows update only their own bank (per-user gradient
            isolation via row masking — exact, since the fit VJP is linear in
            grad_h).

The server cost is constant in K: one merged forward/backward per batch
(paper Table 1, ColA merged row).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ColaConfig, ModelConfig
from repro.core import gl, merge
from repro.core import taps as taps_lib
from repro.core.channel import OffloadChannel
from repro.core.offload import Offloader
from repro.models import model as model_lib
from repro.optim import optimizers as optim_lib
from repro.telemetry import NULL_CONTEXT

Array = jax.Array


def mask_user_rows(data: dict[str, tuple], user_ids: Array, k: int) -> dict:
    """Zero grad_h on rows not belonging to user k. Because the fit gradient is
    linear in grad_h, fitting on masked data gives exactly user k's gradient."""
    out = {}
    for tap, (x, gh) in data.items():
        b_axis = gh.ndim - 3          # (L?, B, S, d)
        shape = [1] * gh.ndim
        shape[b_axis] = gh.shape[b_axis]
        m = (user_ids == k).astype(gh.dtype).reshape(shape)
        out[tap] = (x, gh * m)
    return out


class CollabSession:
    """K users fine-tuning one base model collaboratively (merged training)."""

    def __init__(self, cfg: ModelConfig, cc: ColaConfig, params: dict,
                 key: Array, optimizer=None, lr=1e-3,
                 families: list[str] | None = None,
                 injector=None, policy=None, max_update_norm: float = 1e4,
                 quarantine_after: int = 2, telemetry=None):
        assert cc.mode == "faithful_offload" and cc.merged, \
            "collaboration uses merged faithful-offload training (Alg. 1)"
        self.tm = telemetry if telemetry else None
        self.cfg, self.cc = cfg, cc
        self.base_params = params
        # banks fitted on the offload devices are merged where the base
        # weights live, so the server step stays on the server device
        self._server_sharding = jax.tree.leaves(params)[0].sharding
        self.K = cc.users
        taps = gl.select_taps(cfg, cc.taps)
        # users may choose different adapter families (paper: LowRank-Linear)
        fams = families or [cc.family] * self.K
        assert len(fams) == self.K
        self.user_specs = [
            taps_lib.make_spec(family=f, taps=taps, rank=cc.rank,
                               hidden=cc.hidden, scale=cc.scale)
            for f in fams]
        self.server_spec = gl.make_spec(cfg, cc)   # inject/collect only
        optimizer = optimizer or optim_lib.adamw(lr)
        sites = model_lib.tap_sites(cfg)
        self.offloaders = []
        self.channels: list[OffloadChannel] = []
        for k in range(self.K):
            ad = taps_lib.init_adapter_vars(
                self.user_specs[k], sites, jax.random.fold_in(key, k))
            off = Offloader(self.user_specs[k], ad, optimizer,
                            interval=cc.interval, compress=cc.compress)
            self.offloaders.append(off)
            # each user ships over their own fault domain: one channel per
            # offloader, so a faulted user degrades alone (quarantine +
            # rollback) while the round continues with the survivors.
            self.channels.append(OffloadChannel(
                off, user=k, injector=injector, policy=policy,
                max_update_norm=max_update_norm,
                quarantine_after=quarantine_after, telemetry=self.tm))
        self._server = jax.jit(functools.partial(
            gl.server_step_a, cfg, self.server_spec))
        self._merged_cache = None
        self.step_count = 0

    # ------------------------------------------------------------------
    def merged_model(self) -> dict:
        if self._merged_cache is None:
            p = self.base_params
            for k in range(self.K):
                p = merge.merged_params(
                    self.cfg, p, self.user_specs[k].family_map,
                    jax.device_put(self.offloaders[k].adapters,
                                   self._server_sharding), self.cc.scale)
            self._merged_cache = p
        return self._merged_cache

    def train_step(self, batch: dict, user_ids: Array) -> float:
        """One FTaaS iteration: merged server pass + per-user offloaded fits.

        Every user's push/fit goes through their `OffloadChannel`: transit
        faults are retried, invalid updates are rolled back, and a user whose
        rounds keep failing is quarantined — the round always completes with
        the surviving users, and the merged model only ever folds in
        validated (last-good) banks.
        """
        self.step_count += 1
        params = self.merged_model()
        loss, data, _ = self._server(params, {}, batch)
        updated = False
        for k in range(self.K):
            ch = self.channels[k]
            # per-user offload-round span; the channel's push/fit spans
            # (carrying transport seq ids) nest inside it
            with self._offload_span(ch):
                ch.push(mask_user_rows(data, user_ids, k))
                if ch.fit_round() is not None:
                    updated = True
        if updated:
            self._merged_cache = None
        return float(loss)

    def _offload_span(self, ch):
        if self.tm is None:
            return NULL_CONTEXT
        return self.tm.span("session.offload_round", cat="offload", tid=1,
                            user=ch.user, seq=ch._seq)

    # -- fault-tolerance surface ----------------------------------------
    def bank_versions(self) -> list[int]:
        return [ch.version for ch in self.channels]

    def channel_health(self) -> dict[int, dict]:
        return {k: ch.health() for k, ch in enumerate(self.channels)}

    def reset_channels(self) -> None:
        """Watchdog recovery hook: reset every user's channel (drop in-flight
        buffers, restore last-good banks, lift quarantine)."""
        for ch in self.channels:
            ch.reset()
        self._merged_cache = None
