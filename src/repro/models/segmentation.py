"""Segmentation: split a model's forward pass into dispatchable "kernels"
(program segments) for the FIKIT scheduler.

A service's inference = [embed] + [layer]*L + [head]. The layer segment is
ONE jitted program reused for every layer (the stacked layer params and the
layer index are arguments), so all L dispatches share a KernelID — exactly
the paper's observation that a task repeatedly calls kernels with the same
ID (Fig 5), and the reason SK averaging + runtime feedback exist.

Host work (tokenize / sample / detokenize) runs client-side between
segments — the genuine origin of inter-kernel device idle gaps.
"""
from __future__ import annotations

import time
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.config import DENSE, ENCDEC, HYBRID, MOE, SSM, VLM, ModelConfig
from repro.core.client import Segment
from repro.models import mamba2, moe, rglru, transformer as tfm
from repro.models.layers import rms_norm


def _sync(x):
    jax.block_until_ready(x)
    return x


def _sleep_work(seconds: float) -> Optional[Callable]:
    if seconds <= 0:
        return None

    def work(state):
        time.sleep(seconds)
        return state
    return work


def _positions(S):
    return jnp.arange(S, dtype=jnp.int32)


def _layer_of(stack, i):
    """Layer ``i`` of a stacked layer tree, sliced inside the program that
    uses it: the stack stays the only copy of the weights, and ``i`` is an
    argument, so every layer runs the same compiled program."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), stack)


class SegmentedService:
    """A model packaged as FIKIT-schedulable segments.

    Every segment is a jitted program that takes its parameters as
    arguments (a closed-over array would be baked into the executable as
    a constant, a second copy of the weights on the device). ``programs``
    maps each distinct program to its jitted function. No program or
    segment refers back to the service: a reference cycle would keep the
    params on the device after the service is dropped, until the next
    garbage collection.

    host_gap: host think-time injected after each layer segment (models
    the CPU-side work real serving stacks do between dispatches).
    """

    def __init__(self, cfg: ModelConfig, params, batch: int, seq: int,
                 host_gap: float = 0.0, tail_gap: float = 0.0):
        self.cfg = cfg
        self.params = params
        self.batch = batch
        self.seq = seq
        self.host_gap = host_gap
        self.tail_gap = tail_gap
        self.programs = {}
        self._build()

    # ------------------------------------------------------------- builders
    def _build(self):
        cfg = self.cfg
        if cfg.family in (DENSE, VLM, MOE, SSM):
            self._build_decoder_lm()
        elif cfg.family == HYBRID:
            self._build_hybrid()
        elif cfg.family == ENCDEC:
            self._build_encdec()
        else:  # pragma: no cover
            raise ValueError(cfg.family)

    def _entry(self, name: str, fn, host_work=None) -> Segment:
        """A segment that runs program ``name`` on the whole param tree
        and waits for its result."""
        prog = self.programs[name] = jax.jit(fn)
        params = self.params
        return Segment(f"{self.cfg.name}/{name}",
                       lambda state: _sync(prog(params, state)),
                       host_work=host_work)

    def _stacked_layers(self, name: str, prog, stack, statics=None):
        """One segment per layer of ``stack``, all on program ``prog``;
        ``statics[i]`` (if given) is layer i's static argument."""
        self.programs[name] = prog
        L = jax.tree.leaves(stack)[0].shape[0]
        segs = []
        for i in range(L):
            args = (stack, jnp.asarray(i, jnp.int32))
            if statics is not None:
                args += (statics[i],)
            segs.append(Segment(
                f"{self.cfg.name}/{name}",
                partial(self._run_layer, prog, args),
                host_work=_sleep_work(self.host_gap)))
        return segs

    @staticmethod
    def _run_layer(prog, args, x):
        return _sync(prog(*args, x))

    def _head(self, unpack=lambda state: state) -> Segment:
        cfg = self.cfg
        return self._entry(
            "head", lambda p, state: tfm.unembed(p, unpack(state), cfg),
            host_work=self._sample_work())

    def _build_decoder_lm(self):
        cfg = self.cfg

        def embed(p, batch):
            if cfg.family == VLM:
                patches, tokens = batch
                return tfm.embed_tokens(p, tokens, cfg, patches)
            return tfm.embed_tokens(p, batch, cfg)

        if cfg.family == MOE:
            def layer(stack, i, is_full, x):
                window, chunk = ((cfg.sliding_window, None) if is_full
                                 else (cfg.sliding_window,
                                       cfg.attention_chunk))
                y, _aux = moe.layer_apply(
                    _layer_of(stack, i), x, _positions(x.shape[1]),
                    cfg, window=window, chunk=chunk)
                return y
            prog = jax.jit(layer, static_argnums=(2,))
            pat = cfg.chunk_pattern or 1
            statics = [bool(cfg.chunk_pattern) and (i + 1) % pat == 0
                       for i in range(cfg.num_layers)]
        elif cfg.family == SSM:
            def layer(stack, i, x):
                return mamba2.layer_apply(_layer_of(stack, i), x, cfg)
            prog, statics = jax.jit(layer), None
        else:
            def layer(stack, i, x):
                return tfm.layer_apply(
                    _layer_of(stack, i), x, _positions(x.shape[1]),
                    cfg, window=cfg.sliding_window,
                    chunk=cfg.attention_chunk)
            prog, statics = jax.jit(layer), None

        self.segments = (
            [self._entry("embed", embed)]
            + self._stacked_layers("layer", prog, self.params["layers"],
                                   statics)
            + [self._head()])

    def _build_hybrid(self):
        cfg = self.cfg

        def rec_block(lp, x):
            x = rglru._rec_apply(lp, x, cfg)
            return rglru._mlp_res(lp, x, cfg)

        def attn_block(lp, x):
            h = rms_norm(x, lp["ln1"], cfg.norm_eps)
            x = x + tfm.attn_apply_full(lp["attn"], h,
                                        _positions(x.shape[1]), cfg,
                                        window=cfg.local_window)
            return rglru._mlp_res(lp, x, cfg)

        progs = {"rec": jax.jit(rec_block), "attn": jax.jit(attn_block)}
        self.programs.update(progs)
        segs = [self._entry(
            "embed", lambda p, tokens: tfm.embed_tokens(p, tokens, cfg))]
        for lp, kind in zip(self.params["blocks"],
                            rglru.block_kinds(cfg)):
            segs.append(Segment(
                f"{cfg.name}/{kind}",
                partial(self._run_layer, progs[kind], (lp,)),
                host_work=_sleep_work(self.host_gap)))
        self.segments = segs + [self._head()]

    def _build_encdec(self):
        from repro.models import encdec as ed
        cfg = self.cfg

        def encode(p, batch):
            frames, tokens = batch
            return (ed.encode(p, frames, cfg),
                    tfm.embed_tokens(p, tokens, cfg))

        def dec_layer(stack, i, state):
            enc_out, x = state
            x = ed._dec_layer(_layer_of(stack, i), x,
                              _positions(x.shape[1]), enc_out, cfg)
            return (enc_out, x)

        self.segments = (
            [self._entry("encode", encode)]
            + self._stacked_layers("dec_layer", jax.jit(dec_layer),
                                   self.params["dec_layers"])
            + [self._head(unpack=lambda state: state[1])])

    # -------------------------------------------------------------- helpers
    def _sample_work(self):
        tail = self.tail_gap

        def work(logits):
            # host-side sampling: argmax -> python ints (detokenize analog)
            import numpy as np
            toks = np.asarray(jax.device_get(jnp.argmax(logits[..., :64],
                                                        axis=-1)))
            if tail > 0:
                time.sleep(tail)
            return toks
        return work

    def make_input(self, key=None):
        from repro.models import api
        return api.make_batch(self.cfg, self.batch, self.seq, key)

    def warmup(self):
        """Compile all segment programs once (outside any measurement)."""
        state = self.make_input()
        for seg in self.segments:
            state = seg.fn(state)
        return True
