"""Single-host ColA training session: ties together the server step, the
offloader, parameter merging and the baselines — the reference runtime used by
examples, benchmarks and tests. (The pod-scale pjit runtime wraps the same
``gl`` functions with shardings; see repro/distributed.)

Modes (ColaConfig.mode):
- "faithful_offload": paper Alg. 1. Server computes (x_m, grad_h_m); the
  Offloader fits adapters off-device every I batches. ``merged=True`` folds
  adapters into the base weights for the server pass (zero adapter FLOPs).
- "fused_fit": beyond-paper Mode B. Adapter grads computed in-graph (Prop 1
  equality), optimizer still lives off-device with interval-I accumulation.
- "lora": classic PEFT baseline — same gradients, on-device optimizer.
- "ft": full fine-tuning baseline.
"""
from __future__ import annotations

import functools
from typing import Any

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


class ColaSession:
    def __init__(self, cfg: ModelConfig, cc: ColaConfig, params: dict,
                 key: Array, optimizer=None, lr=1e-3, offload_device=None,
                 injector=None, policy=None, telemetry=None):
        self.tm = telemetry if telemetry else None
        self.cfg, self.cc = cfg, cc
        self.base_params = params
        # the server step runs where the base weights live; adapters coming
        # back from the offload device are placed there, or the next step
        # would follow them to the offload device
        self._server_sharding = jax.tree.leaves(params)[0].sharding
        self.optimizer = optimizer or optim_lib.adamw(lr)
        self.server_spec = gl.make_spec(cfg, cc)
        taps = gl.select_taps(cfg, cc.taps) if cc.mode != "ft" else ()
        self.adapter_spec = taps_lib.make_spec(
            family=cc.family, taps=taps, rank=cc.rank, hidden=cc.hidden,
            scale=cc.scale)
        self.step_count = 0

        if cc.mode == "ft":
            self.opt_state = self.optimizer.init(params)
            self._step = jax.jit(self._ft_step)
            return

        self.adapters = self._on_server(gl.init_adapters(cfg, cc, key))
        if cc.mode in ("faithful_offload", "fused_fit"):
            self.offloader = Offloader(self.adapter_spec, self.adapters,
                                       self.optimizer, interval=cc.interval,
                                       compress=cc.compress,
                                       device=offload_device)
            # Mode A ships payloads over the (possibly unreliable) offload
            # transport; the channel adds retry/validation/versioning and is a
            # pure pass-through when no faults are injected.
            self.channel = OffloadChannel(self.offloader, user=0,
                                          injector=injector, policy=policy,
                                          telemetry=self.tm)
        else:  # lora
            self.opt_state = self.optimizer.init(self.adapters)

        if cc.mode == "faithful_offload":
            self._server = jax.jit(functools.partial(
                gl.server_step_a, cfg, self.server_spec))
        elif cc.mode in ("fused_fit", "lora"):
            self._train_b = jax.jit(functools.partial(
                gl.train_step_b, cfg, self.server_spec))

        self._grad_accum = None
        self._merged_cache: dict | None = None

    # ------------------------------------------------------------------
    def _offload_span(self, ch):
        if self.tm is None:
            return NULL_CONTEXT
        return self.tm.span("session.offload_round", cat="offload", tid=1,
                            user=ch.user, seq=ch._seq)

    def _on_server(self, tree):
        return jax.device_put(tree, self._server_sharding)

    # ------------------------------------------------------------------
    def _effective_params(self) -> dict:
        if self.cc.mode == "faithful_offload" and self.cc.merged:
            if self._merged_cache is None:
                self._merged_cache = merge.merged_params(
                    self.cfg, self.base_params, self.adapter_spec.family_map,
                    self.adapters, self.cc.scale)
            return self._merged_cache
        return self.base_params

    def _ft_step(self, params, opt_state, batch):
        loss, grads, _ = gl.train_step_ft(self.cfg, params, batch)
        updates, opt_state = self.optimizer.update(grads, opt_state, params)
        return loss, optim_lib.apply_updates(params, updates), opt_state

    # ------------------------------------------------------------------
    def step(self, batch: dict) -> float:
        self.step_count += 1
        cc = self.cc
        if cc.mode == "ft":
            loss, self.base_params, self.opt_state = self._step(
                self.base_params, self.opt_state, batch)
            return float(loss)

        if cc.mode == "faithful_offload":
            params = self._effective_params()
            adapters_in = ({} if cc.merged else self.adapters)
            loss, data, _ = self._server(params, adapters_in, batch)
            # one offload round = push + fit; the channel's own push/fit
            # spans nest inside, carrying the transport seq ids
            with self._offload_span(self.channel):
                self.channel.push(data)
                new = self.channel.fit_round()
            if new is not None:
                self.adapters = self._on_server(new)
                self._merged_cache = None   # re-merge from pristine base
            return float(loss)

        if cc.mode == "fused_fit":
            loss, grads, _ = self._train_b(self.base_params, self.adapters, batch)
            # Mode B ships only adapter-gradient-sized tensors; the offload
            # device owns optimizer state and interval accumulation.
            if self._grad_accum is None:
                self._grad_accum = grads
            else:
                self._grad_accum = jax.tree.map(jnp.add, self._grad_accum, grads)
            if self.step_count % cc.interval == 0:
                g = jax.tree.map(lambda a: a / cc.interval, self._grad_accum)
                g = jax.device_put(g, self.offloader.device)
                updates, self.offloader.opt_state = self.optimizer.update(
                    g, self.offloader.opt_state, self.offloader.adapters)
                self.offloader.adapters = optim_lib.apply_updates(
                    self.offloader.adapters, updates)
                self.adapters = self._on_server(self.offloader.adapters)
                self._grad_accum = None
            return float(loss)

        # lora baseline: on-device optimizer
        loss, grads, _ = self._train_b(self.base_params, self.adapters, batch)
        updates, self.opt_state = self.optimizer.update(
            grads, self.opt_state, self.adapters)
        self.adapters = optim_lib.apply_updates(self.adapters, updates)
        return float(loss)

    # ------------------------------------------------------------------
    def reset_channels(self) -> None:
        """Watchdog recovery hook: drop in-flight offload state, restore the
        last-good bank, lift quarantine (no-op for channel-less modes)."""
        ch = getattr(self, "channel", None)
        if ch is not None:
            ch.reset()
            self.adapters = self._on_server(ch.adapters)
            self._merged_cache = None

    def channel_health(self) -> dict:
        ch = getattr(self, "channel", None)
        return {0: ch.health()} if ch is not None else {}

    # ------------------------------------------------------------------
    def inference_params(self) -> dict:
        """Merged params for serving (PEFT merge-for-inference)."""
        if self.cc.mode == "ft":
            return self.base_params
        fams = self.adapter_spec.family_map
        mergeable = {t: w for t, w in self.adapters.items()
                     if fams[t] in ("lowrank", "linear")}
        if len(mergeable) != len(self.adapters):
            return self.base_params   # non-mergeable families stay unmerged
        return merge.merged_params(self.cfg, self.base_params, fams,
                                   mergeable, self.cc.scale)

    def eval_loss(self, batch: dict) -> float:
        params = self._effective_params()
        if self.cc.mode == "faithful_offload" and self.cc.merged:
            loss, _ = model_lib.loss_fn(self.cfg, params, batch)
        elif self.cc.mode == "ft":
            loss, _ = model_lib.loss_fn(self.cfg, params, batch)
        else:
            loss, _ = model_lib.loss_fn(
                self.cfg, params, batch, self.server_spec.with_adapters_only(),
                {"adapters": self.adapters})
        return float(loss)
