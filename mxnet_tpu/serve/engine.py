"""The public serving engine: continuous batching over a paged KV-cache.

``serve.Engine`` drives a gpt() checkpoint (the same parameter dicts
``models/generate.py`` decodes) as a multi-tenant service:

  eng = mx.serve.Engine(params, symbol=net, num_blocks=512)
  req = eng.submit(prompt_ids, max_new_tokens=64)   # may raise QueueFull
  for tok in eng.stream(req):
      ...
  eng.shutdown()

Each pass is one scheduler iteration: at most
``max_prefills_per_step`` prefills (one jit-compiled program per
prompt-length bucket) followed by ONE batched single-token decode over
every running request (one program per batch bucket).  The step loop
runs one pass AHEAD of what it has read: a ``step()`` with pass t
enqueued and unread schedules pass t+1 from what the host can foresee
(positions, who finishes: a request ends by length alone), enqueues it
behind pass t with the rows' input tokens taken from pass t's output ON
THE DEVICE, and only then blocks on pass t and does its bookkeeping, so
the host's turn is no idle time of the device's (``_step_inner``).  What
a caller sees between two calls is one settled snapshot: tokens,
``cache_len``, status, stamps and stats move together when a pass is
read, and every reader from outside the loop reads the unread pass
first.  A prefill skips
whatever block-aligned prefix the content-addressed KV cache already
holds (``MXTPU_SERVE_PREFIX_CACHE``) and runs only the suffix through
a third program family — the *chunk* program, which attends through
the block table to the cached positions; the same program prefills
long prompts one ``MXTPU_SERVE_PREFILL_CHUNK``-token chunk per
iteration, interleaved with decodes.  All shapes are padded to
power-of-two buckets and the block-table width is fixed at
``max_model_len / block_size``, so the number of distinct XLA programs
is bounded by O(log max_batch + log max_model_len) — no per-request
recompiles, the serving analog of ``BucketingModule``'s bucket trick.

The KV-cache is ONE device-resident array pair per engine,
(layers, num_blocks, block_size, kv_heads, head_dim), carved into
blocks by ``kv_block_manager.BlockManager``; the compiled programs
live below this module, in ``serve/programs.py`` (``serve/spec.py`` and
``serve/hybrid.py`` build verify/draft and the hybrid decoders' on it).
Cache-pressure policy lives in ``scheduler.Scheduler`` (preemption +
back-pressure), never here — the engine only executes the schedule it
is handed.

With ``tp=N`` (env ``MXTPU_SERVE_TP``) the same programs run GSPMD-
partitioned over a ``{'tp': N}`` mesh: parameters shard per the
regex partition rules (``parallel.partition``, Megatron/TP layout —
two all-reduces per layer), the KV-cache shards on its head axis so
every chip holds ``kv_heads/N`` of every block, and the exported AOT
artifacts key on the sharding (tp degree + rule digest enter the
fingerprint).  Block accounting, scheduling and the public API are
identical at every tp.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
import traceback

import numpy as np

import jax
import jax.numpy as jnp

from .. import telemetry
from ..aot import export_store as aot_store
from ..aot import warmup as aot_warmup
from ..base import env_flag, env_int
from ..lint.annotations import hot_path
from ..models.generate import (detect_gpt_variant, normalize_gpt_params,
                               reconcile_decode_config)
from ..parallel import partition as partition_mod
from ..parallel.mesh import NamedSharding, PartitionSpec, make_mesh
from ..models.branch import BranchDecoder
from ..models.hybrid import HybridDecoder
from ..models.moe import MoEDecoder, transfer_counts
from ..ops.attention import (PAGED_TILE_TOKENS, SPAN_BLOCK_K, SPAN_BLOCK_Q,
                             SPAN_KERNEL_MIN_SCORES, paged_tile_slots,
                             span_kv_tiles)
from ..telemetry import flight as flight_mod
from ..telemetry import profiling
from ..telemetry import statusz as statusz_mod
from ..telemetry.perf_attrib import PerfAttrib
from ..telemetry.request_trace import RequestTracer
from . import adapters as adapters_mod
from . import hybrid as hybrid_mod
from ..ops import moe as moe_ops
from ..ops import ssm as ssm_ops
from .kv_block_manager import BlockManager, HostKVPool, WindowGroup
from .programs import (TOK_PUT, TOK_PUT1, TOK_TAKE, TOP_LOGPROBS, _ModelCfg,
                       _build_chunk, _build_decode, _build_prefill,
                       _build_restore, _build_token_program, _cfg_fp_fields,
                       _quantize_gpt_params)
from .scheduler import (CANCELLED, FINISHED, REJECTED, WAITING, QueueFull,
                        Request, Scheduler)
from . import spec as spec_mod
from .stats import StatsRecorder

__all__ = ["Engine"]

# Compiled prefill/decode programs shared across Engine instances with
# identical static configs (the serve_bench serial-baseline engine
# reuses every program its batched twin compiled).  The cached
# closures capture ONLY the immutable _ModelCfg — never an Engine —
# so a retired engine (and its multi-GB parameter dict) stays
# collectable while its programs outlive it.
_STEP_CACHE = {}


# per-engine GSPMD placement bundle for tensor-parallel serving (None
# on the single-device path): the tp mesh, the per-parameter
# NamedShardings resolved from the partition rules, the head-sharded
# KV-cache sharding, and the replicated sharding for tokens/positions/
# tables/rng.  Passed to the program builders — like _ModelCfg it holds
# no Engine reference, so _STEP_CACHE still cannot retain a retired
# engine's parameter dict.
# ``scale`` is the int8-KV scale arrays' sharding (head axis, like the
# cache); None outside kv_quant engines
#  ``adapters`` is the LoRA device-stack pytree's shardings (A/B stacks
# shard on the same axes as their parent projections); None outside
# adapter engines
_Shardings = collections.namedtuple("_Shardings",
                                    ["mesh", "params", "cache", "rep",
                                     "scale", "adapters"],
                                    defaults=(None, None))


def _next_bucket(n, cap):
    """Smallest power-of-two >= n, clamped to cap."""
    b = 1
    while b < n:
        b *= 2
    return min(b, cap)


# -- per-request sampling-parameter validation (submit + Engine defaults) ----
def _valid_temperature(t):
    t = float(t)
    if not np.isfinite(t) or t < 0.0:
        raise ValueError(f"temperature must be finite and >= 0 (got {t})")
    return t


def _valid_top_p(p):
    p = float(p)
    if not np.isfinite(p) or not 0.0 < p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1] (got {p})")
    return p


def _valid_top_k(k):
    """None/0 = off; else a positive int (values past the engine's
    ``sample_cap`` behave as the cap — documented in serve.md)."""
    if k is None or k == 0:
        return None
    k = int(k)
    if k < 1:
        raise ValueError(f"top_k must be None/0 or >= 1 (got {k})")
    return k


class _Part:
    """One program of a pass, enqueued and not read yet: its host-bound
    outputs still on the device (``lead``, and ``ok`` the watchdog's
    flag), and what the engine kept from the dispatch for the read."""

    __slots__ = ("reqs", "lead", "ok", "args", "start", "end", "n", "freed",
                 "rec", "blocks")

    def __init__(self, reqs, lead, ok, args, start=0, end=0, n=0):
        self.reqs, self.lead, self.ok, self.args = reqs, lead, ok, args
        # a prefill pass: positions [start, end) of the n to prefill
        self.start, self.end, self.n = start, end, n
        self.freed = 0           # window-group blocks its trim gave back
        self.rec = None          # the step record's pass timer (tracing)
        self.blocks = None       # a denoise pass: (_Block, step, fixed) a row


class _Pass:
    """One scheduler iteration's programs from enqueue to read.
    ``reason`` says how it was enqueued: None behind an unread pass
    (``ahead``), else why nothing was unread (``settled``)."""

    __slots__ = ("reason", "prefills", "decode", "n_prefills", "n_decodes",
                 "queue", "running", "state_slots", "emitted", "freed",
                 "moe", "read")

    def __init__(self, reason, n_prefills, n_decodes, queue, running,
                 state_slots):
        self.reason = reason
        self.prefills, self.decode = [], None
        self.n_prefills, self.n_decodes = n_prefills, n_decodes
        self.queue, self.running = queue, running
        self.state_slots = state_slots
        self.emitted = self.freed = 0
        self.moe = None
        self.read = False


class _Block:
    """A diffusion request's current block as the host foresees it (the
    schedule is static, so no pass's kind or position waits for a read):
    ``host`` the tokens its next pass takes (None: the unread pass is
    denoising it and they are on the device, in the token pool),
    ``fixed`` how many of its first positions came with the prompt,
    ``counts`` the positions each denoise step unmasks while masks are
    left (``models.moe.transfer_counts``), ``step`` the next step
    (``steps``, past them: the store pass), and ``opens`` whether it is
    the first block since the request's admission (its store pass brings
    the first tokens)."""

    __slots__ = ("host", "fixed", "counts", "steps", "step", "opens")

    def __init__(self, dec, fixed, opens=False):
        self.opens = opens
        self.fixed = len(fixed)
        self.host = np.full(dec.block, dec.mask_id, np.int32)
        self.host[:self.fixed] = fixed
        self.counts = [c for c in transfer_counts(dec.block - self.fixed,
                                                  dec.steps) if c]
        self.steps = dec.steps
        self.step = 0

    def next_count(self):
        """Positions the next pass unmasks (0: the store pass)."""
        return self.counts[self.step] if self.step < len(self.counts) else 0

    def launch(self):
        """One pass of this block enqueued: ``(self, its step, fixed)``,
        the step ``steps`` for the store pass."""
        if self.step >= len(self.counts):
            return self, self.steps, self.fixed
        self.step += 1
        self.host = None
        return self, self.step - 1, self.fixed

    def read(self, block, step):
        """Denoise step ``step`` of this block was read: the host holds
        its output, unless a later step was enqueued behind it (that
        one took it on the device)."""
        if step == self.step - 1:
            self.host = np.asarray(block, np.int32)


class Engine:
    """Continuous-batching inference engine over a paged KV-cache.

    Args:
      params: gpt() parameter dict (numpy or jax arrays; quantized and
        fused-qkv checkpoints are normalized at load).
      num_heads / window: decode config not recoverable from weight
        shapes; pass them or pass ``symbol=`` (the trained graph) to
        read both, exactly like ``gpt_generate``.
      block_size: tokens per KV-cache block
        (env ``MXTPU_SERVE_BLOCK_SIZE``, default 16).
      num_blocks: physical blocks in the cache, incl. the reserved
        null block (env ``MXTPU_SERVE_NUM_BLOCKS``, default 512).
      max_batch: decode batch ceiling
        (env ``MXTPU_SERVE_MAX_BATCH``, default 8).
      max_queue: admission-queue bound; ``submit`` beyond it raises
        ``QueueFull`` (env ``MXTPU_SERVE_MAX_QUEUE``, default 64).
      max_model_len: longest prompt+generation length served; defaults
        to the positional-table length (learned positions) or the
        cache capacity at ``max_batch`` concurrency (rope).
      max_prefills_per_step: prompt prefills interleaved per iteration
        ahead of the batched decode (default 1).
      temperature/top_p/top_k/seed: the PER-REQUEST sampling defaults
        (``submit()`` overrides them per request).  0.0/1.0/None is
        greedy argmax — deterministic, which preemption-resume
        equivalence relies on.  Any stochastic default flips the
        engine into sampling mode (see ``sampling``).
      sampling: per-request sampling mode (env ``MXTPU_SERVE_SAMPLING``;
        auto-on when the defaults above are stochastic).  In sampling
        mode temperature/top-p/top-k ride every program as
        ``(B,)``-shaped traced OPERANDS — one bucketed program serves
        any mix of per-request configs (greedy rows included) with
        zero fresh traces, and every emitted token returns its
        logprob (+ top-``TOP_LOGPROBS`` candidates).  Off (the
        default) is the historical greedy-only engine, byte-for-byte:
        same programs, same AOT fingerprints, same tokens.
      sample_cap: top-k/top-p candidate cap of the sampling-mode
        programs (env ``MXTPU_SERVE_SAMPLE_CAP``, default 64): the
        warp ranks the leading ``sample_cap`` logits with one
        ``jax.lax.top_k`` instead of a full-vocab sort and samples
        within them — ``top_k`` values past the cap behave as the
        cap, and a nucleus needing more than ``cap`` candidates is
        truncated there (exact whenever cap >= vocab).
      clock: the one clock of every request stamp, ``time.perf_counter``
        like the spans and the step profiler (tests inject a fake one).
      aot_dir: exported-executable store for AOT restart
        (env ``MXTPU_AOT_DIR``; see mxnet_tpu/aot/).  When set, bucket
        programs are serialized on first build and restarted engines
        load them instead of re-tracing; ``warmup()`` replays a traffic
        manifest (env ``MXTPU_WARMUP_MANIFEST`` records one) so every
        program is ready before the first request.
      tp: tensor-parallel degree (env ``MXTPU_SERVE_TP``, default 1).
        ``tp > 1`` builds a ``{'tp': tp}`` device mesh, shards the
        parameter dict per the partition rules (attention heads and
        MLP hidden split across chips, GSPMD inserting two all-reduces
        per layer) and head-shards the paged KV-cache, so each chip
        holds ``kv_heads/tp`` of every block — per-chip KV bytes drop
        by ``tp`` and a model larger than one chip's HBM serves at
        all.  ``num_heads`` and ``kv_heads`` must divide by ``tp``.
      partition_rules: tensor-parallel sharding rules — a list of
        ``(regex, PartitionSpec)`` pairs, or a string in the
        ``MXTPU_SERVE_PARTITION_RULES`` syntax
        (``parallel.partition.parse_rules``).  Default: the env var,
        else ``parallel.partition.gpt_partition_rules`` keyed to this
        checkpoint's naming.  Ignored at ``tp=1``.
      prefix_cache: content-addressed KV-block sharing across requests
        (env ``MXTPU_SERVE_PREFIX_CACHE``, default on): a new prompt's
        longest block-aligned cached prefix is reused and only the
        suffix is prefilled (RadixAttention-style; see
        ``kv_block_manager`` and docs/how_to/serve.md).
      prefill_chunk: chunked-prefill threshold in tokens (env
        ``MXTPU_SERVE_PREFILL_CHUNK``, default 512): a prompt whose
        uncached remainder exceeds it prefills one chunk per iteration
        interleaved with decode steps, so a very long prompt cannot
        stall the decode batch for a whole-prompt prefill.  0 disables
        chunking (whole-prompt prefills only).
      spec_k: draft-model speculative decoding (env ``MXTPU_SERVE_SPEC``,
        default 0 — off and byte-for-byte inert): each decode iteration
        a small draft model proposes ``spec_k`` tokens per running
        request (one dispatch, the k-step loop unrolled) and the target
        model verifies all ``k+1`` positions in ONE bucketed dispatch,
        emitting the longest agreeing prefix plus one corrected token.
        Greedy engines use exact argmax-prefix acceptance
        (token-identical to plain decode); sampling-mode engines use
        rejection-sampling acceptance — distribution-identical to
        plain sampling at any temperature/top-p/top-k.  See
        ``serve/spec.py`` and docs/how_to/serve.md.
      draft_params: the draft model's gpt() parameter dict (required
        when ``spec_k > 0``; same vocab as the target — token ids
        cross between the two models).  ``draft_num_heads`` /
        ``draft_window`` / ``draft_symbol`` mirror the target-side
        decode-config arguments; ``draft_name`` is the draft
        checkpoint's symbol-name prefix (default: the target's).
      quantize: weight-only quantized serving (env
        ``MXTPU_SERVE_QUANT``, default off — and off is byte-for-byte
        inert): ``"int8"`` quantizes every matmul projection of the
        checkpoint per-output-channel at load
        (``contrib.quantization.quantize_weight``) and the compiled
        programs dequantize on the fly — 4x smaller weight reads on
        the memory-bandwidth-bound decode loop.  Embeddings, norms,
        biases and a tied LM head stay fp.  Tokens may differ from
        the fp engine (weight rounding); greedy agreement is gated in
        serve_bench's quant workload.
      kv_dtype: ``"int8"`` (env ``MXTPU_SERVE_KV_DTYPE``) stores K/V
        cache blocks as int8 with per-slot-per-head f32 scales in a
        small parallel array pair indexed by the same block tables —
        roughly half (bf16) to a quarter (f32) the per-chip KV bytes,
        so the same HBM funds proportionally more in-flight context.
        Block accounting, the prefix cache, COW and truncate are
        untouched (block identity never changes); every program
        quantizes on write and dequantizes inside attention, and
        quantization is per-slot so preemption-by-recomputation stays
        token-stable.  Default: the parameter dtype, unquantized.
      host_kv_bytes: host-DRAM offload tier for the prefix cache (env
        ``MXTPU_SERVE_HOST_KV_BYTES``, default 0 — off and byte-for-
        byte inert: same programs, same AOT fingerprints, same
        tokens).  With a byte budget set, a refcount-0 published block
        reclaimed by the prefix LRU parks its K/V (and int8 scale
        slots) device→host instead of discarding it, and a later radix
        hit on that prefix restores the block host→device — an async
        ``device_put`` dispatched ahead of the first program that
        reads it — instead of recomputing.  DRAM is 10-100x HBM, so
        the prefix cache's effective capacity scales with host memory;
        restored spans are token-identical to recompute by
        construction (content-addressed keys, per-slot quantization).
        The pool runs its own LRU under the budget with the same
        leaf-only radix discipline.
    """

    def __init__(self, params, num_heads=None, window=None, symbol=None,
                 name="gpt", block_size=None, num_blocks=None,
                 max_batch=None, max_queue=None, max_model_len=None,
                 max_prefills_per_step=1, temperature=0.0, top_k=None,
                 top_p=None, sampling=None, sample_cap=None,
                 seed=0, clock=time.perf_counter, aot_dir=None, tp=None,
                 partition_rules=None, tenant_share=None,
                 prefix_cache=None, prefill_chunk=None, spec_k=None,
                 draft_params=None, draft_num_heads=None,
                 draft_window=None, draft_symbol=None, draft_name=None,
                 quantize=None, kv_dtype=None, host_kv_bytes=None,
                 adapters=None, adapter_rank=None,
                 adapter_host_bytes=None):
        # a decoder's DESCRIPTION in place of a gpt() symbol, served
        # through serve/hybrid.py: a hybrid decoder's (models/hybrid.py:
        # layers of two kinds, a state pool beside the K/V), a routed-
        # expert decoder's (models/moe.py: global and window attention
        # layers in two cache groups, experts told which they hold) or a
        # one-branch decoder's (models/branch.py: a layer is a mixer OR a
        # feed-forward part).
        # What it brings is read off the description (its state-space
        # layers, its window layers, its routed blocks), never its class
        self._desc = (symbol if isinstance(symbol, (HybridDecoder,
                                                    MoEDecoder,
                                                    BranchDecoder))
                      else None)
        # the router's counts ride out of every program, and the probe
        # through them (serve/hybrid.py)
        self._routed = (self._desc is not None
                        and hybrid_mod.routed(self._desc))
        # block diffusion (models/moe.py): a decode pass denoises each
        # row's block of ``_block`` positions, tokens are committed a
        # block at a time (_run_denoise / _read_denoise)
        self._diffusion = bool(self._desc is not None
                               and self._desc.diffusion)
        self._block = self._desc.block if self._diffusion else 1
        if self._desc is not None:
            if num_heads not in (None, self._desc.num_heads) or window:
                raise ValueError(
                    "a described decoder carries its own num_heads and "
                    "its own attention windows")
            num_heads, window, name = (self._desc.num_heads, 0,
                                       self._desc.name)
        elif symbol is not None:
            num_heads, window = reconcile_decode_config(symbol, num_heads,
                                                        window)
        if num_heads is None:
            raise ValueError("num_heads is required (pass it, or pass "
                             "symbol= to read it from the trained graph)")
        window = 0 if window is None else int(window)
        if window < 0:
            raise ValueError(f"window must be >= 0 (got {window})")

        self.block_size = (int(block_size) if block_size is not None
                           else env_int("MXTPU_SERVE_BLOCK_SIZE", 16))
        self.num_blocks = (int(num_blocks) if num_blocks is not None
                           else env_int("MXTPU_SERVE_NUM_BLOCKS", 512))
        self.max_batch = (int(max_batch) if max_batch is not None
                          else env_int("MXTPU_SERVE_MAX_BATCH", 8))
        max_queue = (int(max_queue) if max_queue is not None
                     else env_int("MXTPU_SERVE_MAX_QUEUE", 64))

        if self._desc is not None:
            self.spec = hybrid_mod.check_params(self._desc, params)
        else:
            params = normalize_gpt_params(params, name)
            self.spec = detect_gpt_variant(params, num_heads, name)
        if self._desc is not None:
            # what a hybrid engine refuses, by name (docs/how_to/serve.md
            # "Hybrid decoders"): arguments and their env defaults alike
            def _arg(v, env):
                return int(v) if v is not None else env_int(env, 0)

            hybrid_mod.refuse(
                self._desc, prefix_cache=bool(prefix_cache),
                spec_k=_arg(spec_k, "MXTPU_SERVE_SPEC"),
                adapters=_arg(adapters, "MXTPU_SERVE_ADAPTERS"),
                kv_dtype=(kv_dtype
                          or os.environ.get("MXTPU_SERVE_KV_DTYPE")),
                quantize=quantize or os.environ.get("MXTPU_SERVE_QUANT"),
                tp=(int(tp) if tp is not None
                    else env_int("MXTPU_SERVE_TP", 1)),
                host_kv_bytes=_arg(host_kv_bytes,
                                   "MXTPU_SERVE_HOST_KV_BYTES"))
            prefix_cache = False
        self.name = name
        self.num_heads = int(num_heads)
        self.window = window
        # -- sampling mode (params as traced OPERANDS, never trace keys) ----
        # the engine-level temperature/top_p/top_k are per-request
        # DEFAULTS applied at submit(); any stochastic default (or an
        # explicit sampling=True / MXTPU_SERVE_SAMPLING=1) flips the
        # engine into sampling mode, where every program threads
        # (B,)-shaped temperature/top-p/top-k operands and returns
        # per-token logprobs — one program per bucket serves any mix
        # of sampling configs with zero retraces.  sampling=False is
        # the historical greedy engine, byte-for-byte: same programs,
        # same AOT fingerprints, same tokens.
        self.temperature = _valid_temperature(temperature)
        self.top_p = _valid_top_p(1.0 if top_p is None else top_p)
        self.top_k = _valid_top_k(top_k)
        stochastic_defaults = (self.temperature > 0.0 or self.top_p < 1.0
                               or self.top_k is not None)
        if sampling is None:
            sampling = (env_flag("MXTPU_SERVE_SAMPLING", False)
                        or stochastic_defaults)
        self._sampling = bool(sampling)
        if not self._sampling and stochastic_defaults:
            raise ValueError(
                "sampling=False forces the greedy-only programs, which "
                "cannot serve temperature/top_p/top_k defaults — drop "
                "sampling=False or the stochastic defaults")
        if self._diffusion and self._sampling:
            raise ValueError(
                "a diffusion decoder cannot be served with sampling: the "
                "denoise pass unmasks by greedy confidence, and no sampled "
                "schedule is written")
        self.sample_cap = (int(sample_cap) if sample_cap is not None
                           else env_int("MXTPU_SERVE_SAMPLE_CAP", 64))
        if self.sample_cap < 1:
            raise ValueError(
                f"sample_cap must be >= 1 (got {self.sample_cap})")
        # -- quantized serving (weight-only int8 + int8 KV blocks) ---------
        # both default OFF and off is byte-for-byte inert: the traced
        # programs, the warmup grid, the AOT fingerprints and every
        # emitted token are identical to a pre-quant engine's
        if quantize is None:
            quantize = os.environ.get("MXTPU_SERVE_QUANT") or None
        if quantize not in (None, "int8"):
            raise ValueError(
                f"quantize must be None or 'int8' (got {quantize!r})")
        self.quantize = quantize
        if kv_dtype is None:
            kv_dtype = os.environ.get("MXTPU_SERVE_KV_DTYPE") or None
        if kv_dtype not in (None, "int8"):
            raise ValueError(
                f"kv_dtype must be None or 'int8' (got {kv_dtype!r})")
        self._kv_quant = kv_dtype == "int8"
        if self.quantize:
            # per-output-channel int8 + *_wscale vectors; detection ran
            # on the fp checkpoint, the programs dequantize on the fly
            params = _quantize_gpt_params(params, name, self.spec)
        # -- paged LoRA adapter multiplexing (serve/adapters.py) -----------
        # default OFF and off is byte-for-byte inert: no slot operand,
        # unchanged program-cache keys, unchanged AOT fingerprints,
        # identical tokens.  ``adapters`` counts device slots INCLUDING
        # the reserved all-zero base slot 0
        self._adapters = (int(adapters) if adapters is not None
                          else env_int("MXTPU_SERVE_ADAPTERS", 0))
        if self._adapters < 0 or self._adapters == 1:
            raise ValueError(
                f"adapters must be 0 (off) or >= 2 slots including the "
                f"reserved base slot 0 (got {self._adapters})")
        self.adapter_rank = (int(adapter_rank) if adapter_rank is not None
                             else env_int("MXTPU_SERVE_ADAPTER_RANK", 8))
        if self._adapters and self.adapter_rank < 1:
            raise ValueError(
                f"adapter_rank must be >= 1 (got {self.adapter_rank})")
        self.adapter_host_bytes = (
            int(adapter_host_bytes) if adapter_host_bytes is not None
            else env_int("MXTPU_SERVE_ADAPTER_HOST_BYTES", 0)) or None
        adapter_stems = None
        if self._adapters:
            adapter_stems = adapters_mod.gpt_stems(
                name, self.spec["n_layers"], self.spec["swiglu"],
                self.spec["tied"], params)
        # -- tensor-parallel mesh + partition rules ------------------------
        self.tp = (int(tp) if tp is not None
                   else env_int("MXTPU_SERVE_TP", 1))
        if self.tp < 1:
            raise ValueError(f"tp must be >= 1 (got {self.tp})")
        self.mesh = None
        self._shardings = None
        self._rules = None
        self._rules_digest = None
        if self.tp > 1:
            if self.tp > jax.device_count():
                raise ValueError(
                    f"tp={self.tp} exceeds the {jax.device_count()} "
                    f"visible {jax.default_backend()} devices")
            if self.num_heads % self.tp or self.spec["kv_heads"] % self.tp:
                raise ValueError(
                    f"tp={self.tp} must divide num_heads="
                    f"{self.num_heads} and kv_heads="
                    f"{self.spec['kv_heads']} (head-sharded attention "
                    "and KV-cache)")
            if partition_rules is None:
                partition_rules = os.environ.get(
                    "MXTPU_SERVE_PARTITION_RULES") or None
            if isinstance(partition_rules, str):
                self._rules = partition_mod.parse_rules(partition_rules)
            elif partition_rules is not None:
                self._rules = list(partition_rules)
            if not self._rules:
                self._rules = partition_mod.gpt_partition_rules(
                    name=name, axis="tp")
            self._rules_digest = partition_mod.rules_digest(self._rules)
            self.mesh = make_mesh({"tp": self.tp})
            specs = partition_mod.match_partition_rules(self._rules, params)
            rep = NamedSharding(self.mesh, PartitionSpec())
            # LoRA stacks shard on the SAME axes as their parent
            # projections: an out-sharded parent ((tp, None) weight)
            # shards the B stack's d_out axis (A replicated); an
            # in-sharded parent ((None, tp)) shards the A stack's d_in
            # axis (B replicated) — the delta's partial-sum joins the
            # layer's existing all-reduce
            adapter_shardings = None
            if self._adapters:
                adapter_shardings = {}
                for stem in adapter_stems:
                    wspec = specs.get(f"{stem}_weight") or PartitionSpec()
                    out_ax = wspec[0] if len(wspec) > 0 else None
                    in_ax = wspec[1] if len(wspec) > 1 else None
                    adapter_shardings[f"{stem}_A"] = NamedSharding(
                        self.mesh, PartitionSpec(None, None, in_ax))
                    adapter_shardings[f"{stem}_B"] = NamedSharding(
                        self.mesh, PartitionSpec(None, out_ax, None))
                adapter_shardings["scale"] = rep
            self._shardings = _Shardings(
                mesh=self.mesh,
                params=partition_mod.named_shardings(self.mesh, specs),
                adapters=adapter_shardings,
                # each chip holds kv_heads/tp of EVERY block: block
                # accounting (BlockManager) is unchanged, per-chip KV
                # bytes drop by tp
                cache=NamedSharding(self.mesh, PartitionSpec(
                    None, None, None, "tp", None)),
                rep=rep,
                # int8-KV scale arrays shard on the SAME head axis as
                # the cache blocks they dequantize (kv_heads % tp is
                # already enforced above)
                scale=NamedSharding(self.mesh, PartitionSpec(
                    None, None, None, "tp")))
        cache_tokens = (self.num_blocks - 1) * self.block_size
        if max_model_len is None:
            # learned positions cap the servable length at the table;
            # rope has no trained limit, so cap where max_batch peers
            # can still coexist in the cache (pure heuristic — override
            # freely; admission re-checks the cache either way)
            max_model_len = (self.spec["pos_table"]
                             or max(self.block_size,
                                    cache_tokens // max(1, self.max_batch)))
        self.max_model_len = int(min(max_model_len, cache_tokens))
        if (self.spec["pos_table"] is not None
                and self.max_model_len > self.spec["pos_table"]):
            raise ValueError(
                f"max_model_len={self.max_model_len} exceeds the "
                f"positional table ({self.spec['pos_table']})")
        # fixed block-table width: one decode program per batch bucket
        self.table_width = -(-self.max_model_len // self.block_size)

        # -- speculative decoding (serve/spec.py) --------------------------
        self.spec_k = (int(spec_k) if spec_k is not None
                       else env_int("MXTPU_SERVE_SPEC", 0))
        if self.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0 (got {self.spec_k})")
        if self.spec_k:
            # temperature > 0 is served by REJECTION-SAMPLING
            # acceptance (Leviathan/Chen 2023): accept a drafted token
            # with prob min(1, p_target/p_draft), resample from the
            # normalized residual on reject — distribution-identical
            # to plain sampling, so the spec speedup covers stochastic
            # traffic too.  Greedy engines keep the exact argmax-
            # prefix acceptance (byte-identical to plain decode).
            if draft_params is None:
                raise ValueError(
                    "spec_k > 0 requires draft_params (a small gpt() "
                    "checkpoint whose vocab matches the target's)")
        self._spec = None           # DraftWorker, attached below

        # -- host-DRAM KV offload tier (kv_block_manager.HostKVPool) -------
        # default OFF and off is byte-for-byte inert: no restore
        # program family, unchanged warmup grid, unchanged AOT
        # fingerprints, identical tokens
        self.host_kv_bytes = (int(host_kv_bytes)
                              if host_kv_bytes is not None
                              else env_int("MXTPU_SERVE_HOST_KV_BYTES", 0))
        if self.host_kv_bytes < 0:
            raise ValueError(
                f"host_kv_bytes must be >= 0 (got {self.host_kv_bytes})")
        self._host_pool = (HostKVPool(self.host_kv_bytes,
                                      block_tokens=self.block_size)
                           if self.host_kv_bytes else None)
        window_group = None
        if self._desc is not None and self._desc.window_layers:
            # the window layers' group, sized by what it has to hold: a
            # window's blocks for every row of the batch, and the span of
            # the one chunk pass that runs at a time
            chunk = (int(prefill_chunk) if prefill_chunk is not None
                     else env_int("MXTPU_SERVE_PREFILL_CHUNK", 512))
            scratch = (-(-_next_bucket(chunk, self.max_model_len)
                         // self.block_size) + 1) if chunk > 0 else 0
            per_request = -(-self._desc.window // self.block_size) + 1
            window_group = WindowGroup(
                self.max_batch * per_request + scratch + 1,
                self.block_size, self._desc.window, scratch=scratch)
        self.blocks = BlockManager(
            self.num_blocks, self.block_size, prefix_cache=prefix_cache,
            host_pool=self._host_pool,
            # a request of a decoder with state-space layers owns blocks
            # AND one state slot
            state_slots=(self.max_batch if self._desc is not None
                         and self._desc.mamba_layers else 0),
            window_group=window_group)
        # always registered: the eviction path only offloads with a
        # pool attached, but export_blocks (the prefill→decode handoff
        # serializer) gathers device blocks D2H through the same fetch
        # on pool-less prefill replicas too — pure numpy, no program or
        # fingerprint changes, byte-for-byte inert for plain serving
        self.blocks.set_offload_source(self._host_kv_fetch)
        # request-scoped observability: the tracer threads every
        # lifecycle event (scheduler decisions included) into the
        # flight-recorder ring, the optional JSONL export
        # (MXTPU_REQUEST_TRACE) and the Chrome-trace request tracks
        self._rtrace = RequestTracer()
        self._rtrace.on_terminal = self._on_request_terminal
        self.scheduler = Scheduler(self.blocks, self.max_batch, max_queue,
                                   max_prefills_per_step, clock=clock,
                                   trace=self._rtrace,
                                   tenant_share=tenant_share,
                                   prefill_chunk=prefill_chunk,
                                   spec_slots=self.spec_k)
        if self._diffusion and (self.block_size % self._block
                                or self.scheduler.prefill_chunk
                                % self._block):
            raise ValueError(
                f"a diffusion decoder's block of {self._block} must divide "
                f"block_size ({self.block_size}) and prefill_chunk "
                f"({self.scheduler.prefill_chunk}): passes cover whole "
                "blocks")
        self._stats = StatsRecorder(clock=clock)
        self.clock = clock
        self._step_id = 0
        # -- the step loop's look-ahead (see _step_inner) ------------------
        # passes enqueued and not read: one between two step() calls
        # while work is left, two inside a call between enqueueing the
        # next and reading the oldest
        self._flight = collections.deque()
        self._passes = 0            # passes enqueued, lifetime
        # why the pass the NEXT call enqueues starts behind nothing,
        # when that was a decision (None: the engine was idle)
        self._held = None
        # passes by how they were enqueued: {"ahead": n, "settled":
        # {reason: n}} (statusz "step_order")
        self._order = {"ahead": 0, "settled": {}}
        # tokens of passes a reader read between two calls: the next
        # step() returns them with its own
        self._read_emitted = 0
        # step() against a reader on another thread (statusz handlers);
        # _in_step keeps a reader reached from INSIDE a step (an
        # exception's flight dump) from reading mid-step
        self._step_lock = threading.RLock()
        self._in_step = False
        # n>1 sample groups whose siblings wait for the primary's
        # prefill to publish the prompt's blocks (submit() appends from
        # handler threads, the step thread drains)
        self._fanout_lock = threading.Lock()
        self._pending_fanout = []      # guarded-by: _fanout_lock
        # SLO breach -> flight dump: deadline misses always (rate-
        # limited by the recorder), rejection rate when the env
        # threshold is set (fraction of the last 100 terminal requests)
        self._slo_window = collections.deque(maxlen=100)
        try:
            self._reject_rate_thr = float(
                os.environ.get(flight_mod.ENV_REJECT_RATE, "") or 0.0)
        except ValueError:
            self._reject_rate_thr = 0.0
        self._numeric_watch = env_flag("MXTPU_NUMERIC_WATCH", False)

        # place weights (sharded per the rules when tp > 1) and track
        # which device arrays THIS engine materialized: shutdown()
        # deletes exactly those, deterministically, without ever
        # invalidating caller-owned jax arrays that passed through
        self._owned = []
        placed = {}
        for k, v in params.items():
            if self._shardings is not None:
                # device_put straight from the source array: each chip
                # receives only its shard — no transient full-size copy
                # on device 0 (which could OOM exactly the models tp
                # exists to serve)
                arr = jax.device_put(v, self._shardings.params[k])
            else:
                arr = jnp.asarray(v)
            if arr is not v:
                self._owned.append(arr)
            placed[k] = arr
        self.params = placed
        dt = self.params[f"{name}_tok_embed_weight"].dtype
        # paged LoRA slots live in engine-owned device stacks shaped by
        # the checkpoint (A/B in the activation dtype — the base may be
        # int8-quantized, the deltas never are); slot 0 stays all-zero
        self.adapter_store = None
        if self._adapters:
            self.adapter_store = adapters_mod.AdapterStore(
                adapter_stems, self.adapter_rank, self._adapters,
                dtype=np.dtype(str(dt)),
                host_bytes=self.adapter_host_bytes,
                shardings=(None if self._shardings is None
                           else self._shardings.adapters))
        # a described decoder's K/V is stacked over the layers that keep
        # their whole context only (its window layers' group follows below)
        L = (self.spec["n_layers"] if self._desc is None
             else len(self._desc.global_layers))
        # int8 KV blocks store quantized slots plus per-slot-per-head
        # f32 scales in a small parallel array pair indexed by the SAME
        # block ids — BlockManager accounting, the radix prefix cache,
        # COW and truncate are untouched because block identity and
        # refcounts never change
        cache_dt = jnp.dtype(jnp.int8) if self._kv_quant else dt
        shape = (L, self.num_blocks, self.block_size,
                 self.spec["kv_heads"], self.spec["head_dim"])
        sshape = shape[:-1]
        if self._desc is not None and self._desc.kv_flat:
            # flat in the minor axis: a (kv_heads, head_dim) = (8, 64)
            # bf16 tile is padded fourfold on the chip (serve/hybrid.py)
            shape = shape[:3] + (shape[3] * shape[4],)
        self._scale_k = self._scale_v = None
        if self._shardings is not None:
            # allocate the cache BORN sharded: a jnp.zeros-then-reshard
            # would transiently hold the whole cache on device 0, which
            # OOMs exactly the aggregate-HBM-sized configs tp unlocks
            zeros = jax.jit(lambda: jnp.zeros(shape, cache_dt),
                            out_shardings=self._shardings.cache)
            self._cache_k = zeros()
            self._cache_v = zeros()
            if self._kv_quant:
                szeros = jax.jit(lambda: jnp.zeros(sshape, jnp.float32),
                                 out_shardings=self._shardings.scale)
                self._scale_k = szeros()
                self._scale_v = szeros()
        else:
            self._cache_k = jnp.zeros(shape, cache_dt)
            self._cache_v = jnp.zeros(shape, cache_dt)
            if self._kv_quant:
                self._scale_k = jnp.zeros(sshape, jnp.float32)
                self._scale_v = jnp.zeros(sshape, jnp.float32)
        # the per-request state pool of a hybrid decoder, beside the K/V:
        # recurrent states float32, convolution rows in the activation
        # dtype, slot 0 the null slot that padded rows name
        self._state_ssm = self._state_conv = None
        # the window layers' group: its own stack over them, its own
        # (fewer) blocks
        self._cache_wk = self._cache_wv = None
        if window_group is not None:
            wshape = ((len(self._desc.window_layers),
                       window_group.num_blocks) + shape[2:])
            self._cache_wk = jnp.zeros(wshape, cache_dt)
            self._cache_wv = jnp.zeros(wshape, cache_dt)
        # the routed blocks' probe (serve/hybrid.py): the newest passes'
        # first rows in and out of every routed block, and whose rows
        # they were; see routed_probe
        self._probe = None
        self._probe_rows = {"decode": (), "span": None}
        if self._routed:
            # a denoise pass's rows are its rows' block positions
            self._probe = jnp.zeros(
                hybrid_mod.probe_shape(self._desc,
                                       self.max_batch * self._block), dt)
        # the caches a description's programs take behind the K/V pair,
        # in operand order, as this engine's attributes
        self._extra_caches = () if self._desc is None else tuple(
            {"ssm": "_state_ssm", "conv": "_state_conv", "wk": "_cache_wk",
             "wv": "_cache_wv", "probe": "_probe"}[name]
            for name in hybrid_mod.extra_caches(self._desc))
        if self._desc is not None and self._desc.mamba_layers:
            hd = self._desc
            M, S = len(hd.mamba_layers), self.max_batch + 1
            self._state_ssm = jnp.zeros(
                (M, S, hd.mamba_heads, hd.mamba_head_dim, hd.mamba_state),
                jnp.float32)
            self._state_conv = jnp.zeros(
                (M, S) + ssm_ops.conv_rows_shape(hd.mamba_conv,
                                                 hd.conv_dim), dt)
        self._key = jax.random.PRNGKey(seed)
        # the token pool (serve/programs.py): the sampled tokens of the
        # unread pass, on the device for the next pass's operands.  A
        # decode pass's rows head it; behind them one row a prefill of a
        # pass, two passes' worth, so that the pass being enqueued does
        # not write over what its own decode has yet to take
        self._tok_fns = {}
        self._tok_pool = None
        self._tok_row = 0
        if self._diffusion:
            # a denoise pass's blocks, a row each: a prefill puts nothing
            # there (the host knows a request's first block)
            pool = np.zeros((self.max_batch, self._block), np.int32)
            self._tok_pool = jnp.asarray(pool)
        elif not self.spec_k:        # positions are not foreseeable there
            pool = np.zeros(self.max_batch + 2 * max(
                1, self.scheduler.max_prefills_per_step), np.int32)
            self._tok_pool = (jnp.asarray(pool) if self._shardings is None
                              else jax.device_put(pool,
                                                  self._shardings.rep))
        # donating the cache through each step avoids a full cache copy
        # per token; CPU PJRT can't donate (it would warn every call)
        self._donate = (jax.default_backend() != "cpu")
        self._cfg = _ModelCfg(
            name=name, n_layers=self.spec["n_layers"],
            num_heads=self.num_heads,
            head_dim=self.spec["head_dim"], kv_heads=self.spec["kv_heads"],
            pos_table=self.spec["pos_table"], swiglu=self.spec["swiglu"],
            tied=self.spec["tied"], rmsnorm=self.spec["rmsnorm"],
            window=self.window, block_size=self.block_size,
            sampling=self._sampling,
            sample_cap=self.sample_cap if self._sampling else 0,
            numeric_watch=self._numeric_watch,
            kv_quant=self._kv_quant,
            adapters=self._adapters,
            adapter_rank=self.adapter_rank if self._adapters else 0,
            hybrid=(None if self._desc is None
                    else hybrid_mod.hybrid_cfg(self._desc)))
        # every parameter's shape and dtype, once: what _spec_key() needs
        # beyond _ModelCfg to tell two engines' compiled programs apart
        # (the vocabulary, the MLP width: widths no cfg field carries)
        self._params_sig = hash(tuple(sorted(
            (k, tuple(v.shape), str(v.dtype))
            for k, v in self.params.items())))
        # draft worker last among the device placements: params, then
        # the target cache, then the (much smaller) draft side — the
        # same one-model-at-a-time HBM discipline shutdown() preserves
        self._draft_shardings = None
        if self.spec_k:
            from .spec import DraftWorker

            self._spec = DraftWorker(
                self, draft_params, num_heads=draft_num_heads,
                window=draft_window, symbol=draft_symbol,
                name=draft_name or name)
            if self._shardings is not None:
                # the draft replicates under tensor parallelism (its
                # params and cache are small by design); its programs
                # still need mesh-aware jit kwargs so GSPMD sees one
                # consistent layout
                rep = self._shardings.rep
                self._draft_shardings = _Shardings(
                    mesh=self.mesh, params=rep, cache=rep, rep=rep)
        # -- AOT startup wiring (mxnet_tpu/aot/) ---------------------------
        self._aot = (aot_store.ExportStore(aot_dir) if aot_dir is not None
                     else aot_store.default_store())
        self._spec_digest = aot_store.digest(self._aot_base_fp())[:16]
        self._manifest = aot_warmup.ManifestRecorder(
            self._spec_digest, os.environ.get(aot_warmup.ENV_MANIFEST))
        self._warming = False
        self._alive = True
        self._noop_steps = 0
        # per-program performance attribution (telemetry/perf_attrib):
        # cost table fills at program-resolve cadence (default on); its
        # device times come from the step record's pass timer below
        # (telemetry on).  Constructed here — after telemetry.enable()
        # in the usual ordering — because it caches its metric handles
        # at construction (the handle-caching asymmetry), and NEVER
        # enters _spec_key/_aot_base_fp: on or off it leaves tokens,
        # program cache keys and AOT fingerprints byte-identical
        self._perf = PerfAttrib()
        # per-step host-overhead decomposition (telemetry/profiling):
        # same construction ordering + inertness rule as PerfAttrib —
        # caches its histogram handle here, never enters
        # _spec_key/_aot_base_fp.  Default on (MXTPU_STEP_PROFILE=0
        # swaps in the NOOP recorder); with telemetry on it times every
        # part of every pass and hands the times to the cost table
        self._sprof = profiling.make_step_profiler(
            on_pass=self._perf.timed if self._perf.enabled else None)
        # live-state gauges stamped once per step (no-op when telemetry
        # is disabled); cumulative serve counters live in StatsRecorder
        self._tel_queue = telemetry.gauge(
            "mxtpu_serve_queue_depth", "requests waiting for admission")
        self._tel_running = telemetry.gauge(
            "mxtpu_serve_running", "requests in the decode batch")
        self._tel_blocks = telemetry.gauge(
            "mxtpu_serve_blocks_in_use", "KV-cache blocks allocated")
        self._tel_block_util = telemetry.gauge(
            "mxtpu_serve_block_utilization", "KV-cache block fraction used")
        self._tel_preempt = telemetry.gauge(
            "mxtpu_serve_preemptions", "scheduler preemptions (lifetime)")
        self._tel_evict = telemetry.gauge(
            "mxtpu_serve_evictions", "retained-block evictions (lifetime)")
        self._tel_rejected = telemetry.gauge(
            "mxtpu_serve_rejected", "rejected requests (lifetime)")
        self._tel_passes = telemetry.counter(
            "mxtpu_serve_passes_total",
            "passes by how they were enqueued: behind an unread pass "
            "(ahead), or with nothing unread and why (settled)",
            ("order", "reason"))
        if window_group is not None:
            self._tel_group_blocks = telemetry.gauge(
                "mxtpu_serve_kv_blocks_in_use",
                "KV-cache blocks allocated, by layer group", ("group",))
            self._tel_window_freed = telemetry.counter(
                "mxtpu_serve_kv_window_blocks_freed_total",
                "window-group blocks returned when their last position "
                "left the window")
        if self._diffusion:
            self._tel_block_passes = telemetry.counter(
                "mxtpu_serve_block_passes_total",
                "decode-pass rows of a diffusion decoder, by kind: a "
                "denoise step, or the store pass that commits a block",
                ("kind",))
            self._tel_unmasked = telemetry.counter(
                "mxtpu_serve_tokens_unmasked_total",
                "block positions the denoise passes unmasked")
            self._tel_committed = telemetry.counter(
                "mxtpu_serve_blocks_committed_total",
                "blocks the store passes committed")
        if self._routed:
            self._tel_moe_picks = telemetry.counter(
                "mxtpu_serve_moe_picks_total",
                "router picks of real rows, by whether this program "
                "holds the expert", ("held",))
        if self._state_ssm is not None:
            self._tel_state_slots = telemetry.gauge(
                "mxtpu_serve_state_slots_in_use",
                "state-pool slots held by admitted requests")
            self._tel_state_resets = telemetry.counter(
                "mxtpu_serve_state_resets_total",
                "prefill passes that started a slot's state from zero",
                ("reason",))
            self._tel_state_skipped = telemetry.counter(
                "mxtpu_serve_state_updates_skipped_total",
                "state updates the state and convolution kernels skip: "
                "decode rows that name the null slot, times the state "
                "layers")
            self._state_skipped = 0
        telemetry.gauge("mxtpu_serve_blocks_total",
                        "allocatable KV-cache blocks").set(
            self.blocks.total_blocks)
        # live introspection: /statusz shows this engine while it is
        # alive (weakref — a retired engine drops off the page)
        self._statusz_name = statusz_mod.register_weak(self, "serve.engine")

    # -- static config key for the shared program cache ----------------------
    def _spec_key(self):
        # _ModelCfg pins the math; the extras pin the traced SHAPES
        # (cache geometry + dtype), the donation policy, and the
        # sharding layout (tp degree + partition-rule digest) — a tp=2
        # program must never be served to a tp=4 engine
        return (self._cfg, self.num_blocks, self.table_width,
                str(self._cache_k.dtype), self._donate, self.tp,
                self._rules_digest, self.spec_k,
                None if self._spec is None else
                (self._spec.cfg, str(self._spec.cache_k.dtype)),
                # weight-only quant changes the params PYTREE (the
                # *_wscale leaves), so a quantized engine's programs
                # must never be served to an unquantized twin
                self.quantize,
                # the paged-attention lowering is chosen at trace time
                # (env + backend + geometry): a kernel-decode program
                # must never be served to an engine whose env pinned
                # the jnp formulation, and vice versa
                self._paged_impl(),
                # likewise the span attention of the prefill and chunk
                # programs (backend + head size; the buckets that carry
                # the kernel follow from the constants it names)
                self._span_impl(),
                # the parameters' shapes (vocabulary, MLP width): two
                # engines that differ only there must not share compiled
                # programs.  Not in the AOT fingerprint, which keeps the
                # gpt digests where they were
                self._params_sig,
                # a hybrid engine's state pool (slots + 1, dtypes)
                None if self._state_ssm is None else
                (self._state_ssm.shape[1], str(self._state_ssm.dtype),
                 str(self._state_conv.dtype)),
                # a window group (layers, blocks); a routed block's probe
                None if self._cache_wk is None
                else tuple(self._cache_wk.shape[:2]),
                None if self._probe is None else tuple(self._probe.shape))

    def _aot_base_fp(self):
        """The on-disk form of _spec_key(): same fields, JSON-stable,
        plus jax version + backend (aot.fingerprint), so an artifact
        from an incompatible process can never be loaded."""
        # sharding fields enter the fingerprint ONLY at tp > 1: a tp=1
        # engine's digest is unchanged from pre-sharding releases, so
        # an upgraded fleet keeps loading its existing artifacts and
        # manifests instead of silently cold-compiling once per upgrade
        sharded = ({} if self.tp == 1 else dict(
            tp=self.tp, mesh_shape=dict(self.mesh.shape),
            partition_rules=self._rules_digest))
        # like the sharding fields, spec enters the fingerprint ONLY
        # when on: a spec-off engine keeps its pre-spec digests, so an
        # upgraded fleet keeps loading its existing artifacts/manifests
        spec = ({} if self._spec is None else dict(
            spec_k=self.spec_k,
            draft=dict(_cfg_fp_fields(self._spec.cfg),
                       cache_dtype=str(self._spec.cache_k.dtype))))
        # quant fields follow the same only-when-on rule: kv_quant=False
        # leaves the cfg dict (and cache_dtype) exactly as pre-quant
        # releases emitted them, and weight-only off adds no key — an
        # upgraded quant-off fleet keeps its artifacts and manifests.
        # _cfg_fp_fields applies the sampling-mode only-when-on rule:
        # a sampling-off cfg re-emits the historical temperature/top_k
        # trace-key fields, so greedy digests never move
        cfg_d = {k: v for k, v in _cfg_fp_fields(self._cfg).items()
                 if k != "kv_quant" or v}
        draft_d = spec.get("draft")
        if draft_d is not None and not draft_d.get("kv_quant"):
            del draft_d["kv_quant"]
        quant = {} if not self.quantize else dict(quantize=self.quantize)
        # the Mosaic paged-decode kernel follows the only-when-on rule
        # too: "jnp" is the historical program (digests keep), but an
        # exported artifact BAKES the lowering and replays it whatever
        # the env says at load — without this key, a TPU fleet that
        # upgrades into the kernel (or escapes it via
        # MXTPU_PAGED_ATTENTION=jnp after a kernel bug) would silently
        # warm-load the other implementation's artifacts forever
        # The value names the kernel's VERSION, not only the choice: an
        # exported artifact replays the kernel it was traced with (right
        # answers at the old speed) and the fingerprint is the store's
        # only version, so it moves with the kernel: "pallas-stacked"
        # (PR 27) read the stacked cache in place one block a grid step;
        # since PR 30 the walk folds a tile of PAGED_TILE_TOKENS
        # positions a step, gpt and hybrid engines alike
        paged = ({} if self._paged_impl() != "pallas"
                 else dict(paged_attention=f"pallas-tile{PAGED_TILE_TOKENS}"))
        # the span kernel of the prefill and chunk programs, only-when-on
        # and versioned by its tiles for the same reason
        span = ({} if self._span_impl() != "kernel" else dict(
            span_attention=f"pallas-q{SPAN_BLOCK_Q}k{SPAN_BLOCK_K}"
                           f"-from{SPAN_KERNEL_MIN_SCORES}"))
        # a hybrid engine's state pool and vocabulary (only-when-on)
        state = ({} if self._state_ssm is None else dict(
            state_slots=int(self._state_ssm.shape[1]),
            state_dtypes=[str(self._state_ssm.dtype),
                          str(self._state_conv.dtype)]))
        # the convolution rows in lane rows (ops.ssm.conv_rows_shape),
        # only-when-on: an artifact traced on the flat pool takes other
        # operands and, on a TPU, bakes the XLA gather and scatter
        if self._state_conv is not None and self._state_conv.ndim == 4:
            state.update(conv_rows=[int(n)
                                    for n in self._state_conv.shape[2:]])
        if self._cache_wk is not None:
            state.update(window_group=[int(n)
                                       for n in self._cache_wk.shape[:2]])
        if self._probe is not None:
            state.update(routed_probe=[int(n) for n in self._probe.shape])
        return aot_store.fingerprint(
            subsystem="serve", cfg=cfg_d,
            num_blocks=self.num_blocks, table_width=self.table_width,
            cache_dtype=str(self._cache_k.dtype), donate=self._donate,
            **sharded, **spec, **quant, **paged, **span, **state)

    def _paged_impl(self):
        """The paged-attention implementation this engine's programs
        trace ("pallas" or "jnp") — resolved from the env/backend/cache
        geometry exactly as ``ops.attention.paged_attention`` will."""
        from ..ops.attention import flat_paged_impl, resolve_paged_impl
        if self._desc is not None and self._desc.kv_flat:
            return flat_paged_impl(self.block_size, self.spec["kv_heads"],
                                   self.spec["head_dim"])
        return resolve_paged_impl(self.block_size,
                                  self.spec["head_dim"])

    def _span_impl(self, rows=None, keys=None):
        """The branch ``ops.attention.masked_attention`` traces for a
        prefill or chunk pass of ``rows`` rows over ``keys`` key
        positions ("kernel" or "dense"); without them, whether ANY of
        this engine's programs carries the span kernel: its largest
        prefill and chunk buckets say (the cap, and the power of two
        under a cap the kernel's blocks do not tile)."""
        from ..ops.attention import resolve_span_impl
        if rows is not None:
            return resolve_span_impl(rows, keys, self.spec["head_dim"])
        view = self.table_width * self.block_size
        top = lambda cap: {cap, 1 << (cap.bit_length() - 1)}
        passes = ([(p, p) for p in top(self.max_model_len)]
                  + [(c, view) for c in top(self._chunk_cap())])
        return ("kernel" if any(self._span_impl(r, k) == "kernel"
                                for r, k in passes) else "dense")

    # -- public API ----------------------------------------------------------
    def submit(self, prompt, max_new_tokens=64, deadline_s=None,
               tenant=None, trace_id=None, handoff=False,
               temperature=None, top_p=None, top_k=None, n=1,
               logprobs=0, adapter_id=None):
        """Queue one generation request; returns its ``Request`` handle.

        Raises ``QueueFull`` when the admission queue is at capacity
        (back-pressure — retry later).  A request that could never fit
        (longer than ``max_model_len`` or the whole cache) is returned
        already REJECTED rather than queued to deadlock.

        ``tenant`` labels the request for fair-share admission and the
        per-tenant telemetry series; ``trace_id`` pre-stamps the trace
        identity (a fleet router propagates one so a request retried
        across replicas stitches into a single cross-process timeline);
        ``handoff`` marks a prefill→decode handoff ingest (the decode
        replica's re-submission) for the admit trace event and the
        scheduler's ``waiting_handoffs`` load signal.

        ``temperature``/``top_p``/``top_k`` are PER-REQUEST sampling
        params (None defers to the engine defaults): on a sampling-mode
        engine they ride the decode batch as traced operands, so any
        mix of configs shares one bucketed program — a greedy-only
        engine (``sampling=False``) rejects non-greedy values with
        ``ValueError``.  ``n > 1`` serves that many independent samples
        of the same prompt, sharing the prompt's radix-cached prefix
        blocks copy-on-write (one prefill pays for all ``n``; the
        handles are on ``req.samples``).  ``logprobs`` (0..5) returns
        that many top-logprob candidates per emitted token alongside
        each token's own logprob (``req.token_logprobs`` /
        ``req.top_logprobs``).

        ``adapter_id`` serves the request through a registered LoRA
        adapter (adapters mode only — ``Engine(adapters=S)`` /
        ``MXTPU_SERVE_ADAPTERS``): the request pins the adapter's
        device slot until it terminates and its rows add the adapter's
        low-rank delta inside the SAME bucketed programs base rows use
        (the slot index is a traced operand — any adapter mix shares
        one program with zero retraces).  Unknown ids raise
        ``ValueError``; a fully-pinned slot table rejects with the
        retriable ``adapter_slots`` reason.
        """
        if not self._alive:
            raise RuntimeError("engine is shut down")
        temperature = (self.temperature if temperature is None
                       else _valid_temperature(temperature))
        top_p = self.top_p if top_p is None else _valid_top_p(top_p)
        top_k = self.top_k if top_k is None else _valid_top_k(top_k)
        logprobs = int(logprobs)
        if not 0 <= logprobs <= TOP_LOGPROBS:
            raise ValueError(
                f"logprobs must be in [0, {TOP_LOGPROBS}] "
                f"(got {logprobs})")
        n = int(n)
        if not 1 <= n <= 64:
            raise ValueError(f"n must be in [1, 64] (got {n})")
        if not self._sampling and (temperature > 0.0 or top_p < 1.0
                                   or top_k is not None or logprobs):
            raise ValueError(
                "per-request sampling/logprobs require a sampling-mode "
                "engine (Engine(sampling=True) / MXTPU_SERVE_SAMPLING=1 "
                "or stochastic engine defaults) — greedy-only engines "
                "keep the historical programs byte-for-byte")
        if n > 1 and not self.blocks.prefix_cache:
            raise ValueError(
                "n > 1 requires the prefix cache (siblings share the "
                "prompt's radix-cached blocks copy-on-write — one "
                "prefill, n samples)")
        if adapter_id is not None:
            if not self._adapters:
                raise ValueError(
                    "adapter_id requires an adapters-mode engine "
                    "(Engine(adapters=S) / MXTPU_SERVE_ADAPTERS) — "
                    "adapters-off engines keep the historical programs "
                    "byte-for-byte")
            if (not isinstance(adapter_id, str)
                    or not self.adapter_store.known(adapter_id)):
                raise ValueError(f"unknown adapter: {adapter_id!r}")
        kw = dict(deadline_s=deadline_s, tenant=tenant, handoff=handoff,
                  temperature=temperature, top_p=top_p, top_k=top_k,
                  logprobs=logprobs, adapter_id=adapter_id)
        req = Request(prompt, max_new_tokens, **kw)
        if self._diffusion:
            # a diffusion decoder prefills whole blocks and opens the
            # first with the prompt's tail; the mask id is its own
            if req.prompt.size < self._block:
                raise ValueError(
                    f"a diffusion decoder's prompt needs at least one "
                    f"block ({self._block} tokens; got {req.prompt.size})")
            if (req.prompt == self._desc.mask_id).any():
                raise ValueError(
                    f"the prompt holds the mask id {self._desc.mask_id}, "
                    "which the denoise passes read as a masked position")
            req.block = self._block
        if trace_id:
            req.trace_id = str(trace_id)
        if n > 1:
            sibs = []
            for i in range(1, n):
                s = Request(prompt, max_new_tokens, **kw)
                s.group, s.sample_index = req.rid, i
                if trace_id:
                    s.trace_id = str(trace_id)
                sibs.append(s)
            req.group, req.sample_index = req.rid, 0
            req.samples = [req] + sibs
        if req.target_len() > self.max_model_len:
            for r in (req.samples or [req]):
                self.scheduler._reject(r, "exceeds_max_len")
            return req
        if adapter_id is not None:
            # every row (primary + siblings) pins the slot once: the
            # pin survives preemption (preempt never fires the terminal
            # trace hook) and drops in _on_request_terminal.  All slots
            # pinned is TRANSIENT capacity pressure — the retriable
            # adapter_slots rejection (fleet replicas 503, not 400)
            try:
                for r in (req.samples or [req]):
                    r.adapter_slot = self.adapter_store.acquire(adapter_id)
            except adapters_mod.NoAdapterSlots:
                for r in (req.samples or [req]):
                    self.scheduler._reject(r, "adapter_slots")
                return req
        try:
            out = self.scheduler.submit(req)
        except QueueFull:
            self._stats.on_reject()      # back-pressure event counter
            if req.samples:
                for s in req.samples[1:]:
                    # each sibling is one more back-pressure event —
                    # the Prometheus series and the rejection-rate
                    # breach window must see the whole group
                    self.scheduler._reject(s, "queue_full")
                    self._stats.on_reject()
            raise
        if req.samples:
            if req.status == REJECTED:
                for s in req.samples[1:]:
                    self.scheduler._reject(s, req.reject_reason
                                           or "rejected")
            else:
                # siblings queue ENGINE-side until the primary's
                # prefill publishes the prompt's blocks — only then
                # does their radix walk share the whole block-aligned
                # prefix (released by _release_fanout each step)
                with self._fanout_lock:
                    self._pending_fanout.append((req,
                                                 list(req.samples[1:])))
        return out

    def step(self):
        """Read one pass (admit + prefill, then one batched decode), with
        the next enqueued behind it first.  Returns the number of tokens
        that became visible since the last call.

        An unhandled exception dumps the flight-recorder ring to
        ``MXTPU_FLIGHT_DIR`` before propagating — the post-mortem
        exists even when nobody had tracing on."""
        if not self._alive:
            # caller usage error, not an engine failure: raise without
            # the force-dump (a retry loop on a dead engine must not
            # write one full post-mortem per call)
            raise RuntimeError("engine is shut down")
        try:
            with self._step_lock:
                self._in_step = True
                try:
                    return self._step_inner()
                finally:
                    self._in_step = False
        except Exception:
            rec = flight_mod.recorder()
            rec.record("error", site="engine.step",
                       error=traceback.format_exc(limit=4))
            # spec/sharding digests identify WHICH compiled (possibly
            # sharded) program was live when the process died
            rec.dump("engine_exception", force=True,
                     extra={"traceback": traceback.format_exc(limit=30),
                            "spec_digest": self._spec_digest,
                            "tp": self.tp,
                            "sharding_rules_digest": self._rules_digest})
            raise

    def _has_pending_fanout(self):
        with self._fanout_lock:
            # mxtpu-lint: disable=host-sync (a host list, no device value)
            return bool(self._pending_fanout)

    def _release_fanout(self):
        """Move n>1 siblings into the scheduler once their primary's
        prefill has published the prompt's blocks: each sibling's
        radix walk then reuses the whole block-aligned prefix
        copy-on-write (the final span recomputes into a fresh private
        block — recomputation is the copy), so n samples pay ONE
        prefill however the admission interleaves."""
        with self._fanout_lock:
            if not self._pending_fanout:
                return
            pending, self._pending_fanout = self._pending_fanout, []
        keep = []
        for primary, sibs in pending:
            if not (primary.tokens or primary.flight_tokens
                    or primary.done):
                keep.append((primary, sibs))
                continue
            rest = []
            for i, s in enumerate(sibs):
                if self.scheduler.queue_depth >= self.scheduler.max_queue:
                    rest = sibs[i:]      # queue full: retry next step
                    break
                try:
                    self.scheduler.submit(s)
                except QueueFull:
                    # raced a handler thread into the last queue slot:
                    # the scheduler already counted + traced the
                    # rejection — finalize the handle and count the
                    # back-pressure event like any other queue-full
                    s.status = REJECTED
                    s.reject_reason = "queue_full"
                    s.finish_t = self.clock()
                    self._stats.on_reject()
            if rest:
                keep.append((primary, rest))
        if keep:
            with self._fanout_lock:
                self._pending_fanout = keep + self._pending_fanout

    @hot_path
    def _step_inner(self, read_only=False):
        """One call of the step loop, which runs one pass ahead of what
        it has read.  With pass t enqueued and unread, the call

        1. schedules pass t+1 from what the host can foresee (a row one
           position on, ``Request.next_pos``; a row whose last token pass
           t produces is not in it, ``Request.finishing``) and enqueues
           it behind pass t, the rows' input tokens taken from pass t's
           outputs on the device (the token pool);
        2. blocks on pass t's outputs and does pass t's host work:
           tokens, stamps, stats, request trace, finishes, gauges;
        3. returns pass t's emitted count.  What the caller does next
           runs while the device works on t+1.

        With nothing unread the call first enqueues pass t itself.  Depth
        0 is the same loop: a pass whose operands need values only the
        host will have is enqueued with nothing unread (``_Pass.reason``
        says why: the engine was idle, a speculative engine, a schedule
        that must preempt, host-tier restores, a reader from outside the
        loop).  ``read_only``: the call of a reader, which enqueues
        nothing."""
        self._step_id += 1
        # the one step instrument (telemetry/profiling.py): begin/enter/
        # wait/commit tile the call into phases; with telemetry on the
        # same intervals are the serve.step > serve.<phase> | (serve.
        # prefill | serve.decode > serve.<phase>) spans, and note() hangs
        # the counts on them
        sprof = self._sprof
        sprof.begin(self._step_id)
        flight, done, launched = self._flight, None, False
        if not flight and not read_only:
            first, launched = self._launch(None), True
            if first is not None and first.read:
                done = first         # a speculative engine's: read already
        if flight:
            done = flight[0]
            if len(flight) == 1 and not read_only:
                if launched:
                    sprof.enter("schedule")
                self._launch(done)
            self._read(done)
        sprof.enter("callbacks")
        emitted = done.emitted if done is not None else 0
        n_pre, n_dec = ((done.n_prefills, done.n_decodes)
                        if done is not None else (0, 0))
        if done is not None:
            # scheduler decisions ride the flight ring (bounded, always
            # on) so post-mortems see the recent schedule; with the host
            # tier live its occupancy rides along (off-path records stay
            # byte-identical)
            step_fields = dict(
                id=self._step_id, prefills=n_pre, decodes=n_dec,
                queue=self.scheduler.queue_depth,
                blocks_in_use=self.blocks.blocks_in_use)
            if self._host_pool is not None:
                step_fields["host_kv_entries"] = len(self._host_pool)
                step_fields["host_kv_bytes"] = self._host_pool.bytes_used
            flight_mod.recorder().record("step", **step_fields)
            self._noop_steps = 0
        elif not flight:
            self._noop_steps += 1
            if self._noop_steps > 1000 and self.scheduler.has_work():
                raise RuntimeError(
                    "scheduler stalled: work queued but 1000 consecutive "
                    "steps scheduled nothing (cache/queue misconfigured?)")
        self._stats.on_step(emitted, decode_batch=n_dec)
        self._perf.on_step(emitted, timed=sprof.tracing)
        if self._spec is not None:
            # bound the draft ingest ledger by the LIVE running set: a
            # request that leaves the engine between decodes (preempted,
            # then deadline-rejected or cancelled) never reaches the
            # forget() in _run_spec_decode.  A pruned-then-resumed
            # request simply re-ingests.
            self._spec.prune({r.rid for r in self.scheduler.running})
        self._tel_queue.set(self.scheduler.queue_depth)
        self._tel_running.set(len(self.scheduler.running))
        self._tel_blocks.set(self.blocks.blocks_in_use)
        self._tel_block_util.set(self.blocks.utilization())
        self._tel_preempt.set(self.scheduler.preemptions)
        self._tel_evict.set(self.blocks.evictions)
        self._tel_rejected.set(self.scheduler.rejections)
        if self._state_ssm is not None:
            self._tel_state_slots.set(self.blocks.state_slots_in_use)
        groups = None
        freed = done.freed if done is not None else 0
        if self.blocks.window is not None:
            groups = {"global": self.blocks.blocks_in_use,
                      "window": self.blocks.window.blocks_in_use}
            for name, n in groups.items():
                self._tel_group_blocks.labels(group=name).set(n)
            self._tel_window_freed.inc(freed)
        if done is not None and done.moe is not None:
            picks, held = int(done.moe[0]), int(done.moe[1])
            self._tel_moe_picks.labels(held="yes").inc(held)
            self._tel_moe_picks.labels(held="no").inc(picks - held)
        left = None
        if sprof.tracing:
            # the queue and the batch as the schedule of the pass this
            # call read left them; preemptions is the lifetime count: a
            # reader takes differences
            queue, running = ((done.queue, done.running)
                              if done is not None else
                              (self.scheduler.queue_depth,
                               len(self.scheduler.running)))
            left = int(self.has_work())
            sprof.note(queue=queue, running=running,
                       blocks_in_use=self.blocks.blocks_in_use,
                       emitted=emitted,
                       preemptions=self.scheduler.preemptions,
                       work_left=left,
                       ahead=int(done is not None
                                 and done.reason is None))
            if self._state_ssm is not None:
                # slots held as that pass was scheduled
                sprof.note(state_slots=(
                    done.state_slots if done is not None
                    else self.blocks.state_slots_in_use))
            if groups is not None:
                # what each layer group holds as the step ends, and what
                # the window group gave back to the pass it read
                sprof.note(blocks_global=groups["global"],
                           blocks_window=groups["window"],
                           window_blocks_freed=freed)
        sprof.commit(emitted, prefills=n_pre, decodes=n_dec, work_left=left)
        emitted += self._read_emitted
        self._read_emitted = 0
        return emitted

    @hot_path
    def _launch(self, behind):
        """Schedule one pass and enqueue its programs: behind the unread
        pass ``behind`` where the schedule can be made without reading it,
        else after reading it here (a victim of preemption resumes from
        every token it has, and a host-tier restore is dispatched into a
        settled cache).  Returns the pass, or None when the schedule is
        empty.  A speculative engine's pass is read inside its own launch
        (a verify yields 1..k+1 tokens: nothing of the next pass is
        foreseeable) and comes back read."""
        sprof = self._sprof
        self._release_fanout()
        reason = None
        sched = self.scheduler.schedule(preempt=behind is None)
        if sched is None:
            self._read(behind)
            reason = "preempt"
            sprof.enter("schedule")
            sched = self.scheduler.schedule()
        prefills, decodes = sched
        if self._host_pool is not None:
            # host-tier hits allocated by this schedule() queue their
            # restores; dispatch them NOW, before the first prefill/
            # decode program that reads the blocks
            if (behind is not None and not behind.read
                    and self.blocks.has_pending_restores()):
                self._read(behind)
                reason = "restore"
                sprof.enter("schedule")
            self._restore_pending()
        # blocks for this iteration are all held right now — the honest
        # high-water sample (post-drain reads would be ~0)
        self._stats.on_utilization(self.blocks.utilization())
        if not prefills and not decodes:
            return None
        if behind is None:
            reason = ("spec" if self._spec is not None
                      else self._held or "idle_start")
        self._held = None
        self._passes += 1
        p = _Pass(reason, len(prefills), len(decodes),
                  self.scheduler.queue_depth, len(self.scheduler.running),
                  self.blocks.state_slots_in_use
                  if self._state_ssm is not None else 0)
        if reason is None:
            self._order["ahead"] += 1
        else:
            self._order["settled"][reason] = \
                self._order["settled"].get(reason, 0) + 1
        self._tel_passes.labels(
            order="ahead" if reason is None else "settled",
            reason=reason or "").inc()
        if sprof.tracing:
            sprof.reason = reason        # carried by the pass's parts
        for req in prefills:
            sprof.enter("prefill_dispatch", rid=req.rid)
            # the per-iteration prefill token budget is shared with the
            # decode slots: each decode slot writes up to 1 + spec_k
            # positions this step (one, without speculative decoding; a
            # diffusion decoder's block), so a chunk shrinks by the
            # batch's worst-case count
            p.prefills.append(self._run_prefill(
                req, decode_slots=sum(map(self.scheduler.ahead, decodes))))
        if self._spec is not None:
            self._read(p)        # the prefills: their tokens feed the draft
            if decodes:
                sprof.enter("decode_dispatch")
                p.emitted += self._run_spec_decode(decodes)
            return p
        if decodes:
            sprof.enter("decode_dispatch")
            p.decode = self._run_decode(decodes)
        self._flight.append(p)
        return p

    @hot_path
    def _read(self, p):
        """Block on pass ``p``'s outputs and do its host work: from here
        its tokens, positions, finishes and stats are what a caller
        sees.  Part by part, so that a read that raises half way is taken
        up where it stopped."""
        while p.prefills:
            p.emitted += self._read_prefill(p, p.prefills[0])
            del p.prefills[0]
        if p.decode is not None:
            p.emitted += self._read_decode(p, p.decode)
            p.decode = None
        p.read = True
        if self._flight and self._flight[0] is p:
            self._flight.popleft()

    def _read_flight(self):
        """What every reader from outside the step loop does first: read
        the passes still unread, so that tokens, cache and state pools
        agree in what it sees.  A reader reached from inside a step, or
        on another thread while a step runs, sees that step's snapshot
        as it always has."""
        if not self._flight or self._in_step or not self._alive:
            return
        if not self._step_lock.acquire(blocking=False):
            return
        try:
            self._in_step = True
            while self._flight:
                self._read_emitted += self._step_inner(read_only=True)
            if self.scheduler.has_work():
                self._held = "reader"
        finally:
            self._in_step = False
            self._step_lock.release()

    def _kv_tiles(self, reqs, bucket):
        """How much of the block table one layer's paged-attention call
        of this decode pass walks, in tiles of ``PAGED_TILE_TOKENS``
        positions: ``kv_tiles`` over the live rows' contexts (the band
        only, under a sliding window), ``kv_tiles_table`` what a walk of
        every row's whole table would visit."""
        slots = paged_tile_slots(self.block_size)
        span, window = slots * self.block_size, self.window
        walked = 0
        for req in reqs:
            ctx = req.next_pos() + self._block
            walked += -(-ctx // span) - (max(ctx - window, 0) // span
                                         if window else 0)
        return {"kv_tiles": walked,
                "kv_tiles_table": bucket * -(-self.table_width // slots)}

    def has_work(self):
        """Whether ``step()`` still has anything to do: a pass unread,
        scheduler queues/batches, OR n>1 siblings awaiting release — a
        step-loop driver that only polled ``scheduler.has_work()`` would park
        with fanout siblings still pending (the fleet replica's pump
        reads this)."""
        return (len(self._flight) > 0 or self.scheduler.has_work()
                or self._has_pending_fanout())

    def run(self):
        """Pump ``step()`` until every queued request resolves."""
        while self.has_work():
            self.step()

    def stream(self, req):
        """Yield ``req``'s tokens as they are generated, pumping the
        engine as needed (every co-scheduled request advances too)."""
        sent = 0
        while True:
            while sent < len(req.tokens):
                yield int(req.tokens[sent])
                sent += 1
            if req.done or not self.has_work():
                return
            self.step()

    def stats(self):
        """Immutable ``ServeStats`` snapshot of the engine right now (a
        pass still unread is read first, as by every reader below)."""
        self._read_flight()
        return self._stats.snapshot(self.scheduler, self.blocks)

    # -- SLO breach detection (flight-recorder triggers) ---------------------
    def _on_request_terminal(self, req, name, args):
        """Runs on every request's terminal trace event: a deadline
        miss dumps the flight ring immediately (rate-limited), and a
        rejection rate over ``MXTPU_FLIGHT_REJECT_RATE`` across the
        recent-terminal window dumps too."""
        slot = getattr(req, "adapter_slot", 0)
        if slot and self.adapter_store is not None:
            # drop the request's adapter pin exactly once per lifetime
            # (terminal events never fire twice for one request; the
            # zeroed slot makes a double-call a no-op anyway)
            self.adapter_store.release(slot)
            req.adapter_slot = 0
        rejected = name == "rejected"
        self._slo_window.append(1 if rejected else 0)
        if rejected and args.get("reason") == "deadline":
            flight_mod.recorder().dump(
                "deadline_miss", extra={"rid": req.rid,
                                        "deadline_s": req.deadline_s})
        thr = self._reject_rate_thr
        if thr and len(self._slo_window) >= 20:
            rate = sum(self._slo_window) / len(self._slo_window)
            if rate >= thr:
                flight_mod.recorder().dump(
                    "rejection_rate",
                    extra={"rate": round(rate, 4), "threshold": thr,
                           "window": len(self._slo_window)})

    # -- live introspection (/statusz provider) ------------------------------
    def statusz(self):
        """Live engine state for the ``/statusz`` endpoint: in-flight
        requests with ages and phases, queue/cache occupancy, program
        and AOT-store state."""
        self._read_flight()
        now = self.clock()
        reqs = []
        mid_prefill = {id(r) for r in self.scheduler.prefilling}
        for req in (list(self.scheduler.running)
                    + list(self.scheduler.prefilling)
                    + list(self.scheduler.waiting)):
            if req.status == WAITING:
                phase = "queued" if req.n_preemptions == 0 else "preempted"
            elif id(req) in mid_prefill or not req.tokens:
                phase = "prefill"
            else:
                phase = "decode"
            reqs.append({
                "rid": req.rid, "trace_id": req.trace_id,
                "tenant": req.tenant, "status": req.status, "phase": phase,
                "age_s": (round(now - req.submit_t, 3)
                          if req.submit_t is not None else None),
                "prompt_tokens": int(req.prompt.size),
                "generated": len(req.tokens),
                "target": req.target_len(),
                # how a mid-prefill request is progressing: slots
                # reused from the prefix cache at admission, slots
                # written so far, and the admission-time prefill goal
                # (None while waiting)
                "cached_tokens": req.cached_prefix_len,
                "host_tokens": req.host_restored_len,
                "prefill_done": int(req.cache_len),
                "prefill_target": req.prefill_target,
                "n_preemptions": req.n_preemptions})
        aot = {"dir": getattr(self._aot, "dir", None)}
        if self._aot is not None:
            entries = self._aot.entries()
            aot.update(artifacts=len(entries),
                       bytes=sum(b for _, b in entries))
        return {
            "alive": self._alive,
            "steps": self._step_id,
            # passes by how they were enqueued: behind an unread pass
            # (ahead), or with nothing unread and why (settled)
            "step_order": {"ahead": self._order["ahead"],
                           "settled": dict(self._order["settled"])},
            "queue_depth": self.scheduler.queue_depth,
            "running": len(self.scheduler.running),
            "in_flight": reqs,
            "completed": self._stats.completed,
            "preemptions": self.scheduler.preemptions,
            "reject_reasons": dict(self.scheduler.reject_reasons),
            "tenants": self.scheduler.tenant_stats(),
            "kv_blocks": self.blocks.occupancy(),
            # the prefix-cache section an operator reads to explain a
            # cache-cold replica (also nested in kv_blocks.prefix_cache)
            "prefix_cache": self.blocks.prefix_stats(),
            "kv_cache": self.kv_cache_stats(),
            # a routed-expert decoder's two layer groups: blocks held,
            # free, requests, the window and what it freed (None else)
            "kv_groups": self.blocks.group_stats(),
            # a hybrid decoder's state pool: slots, in use, bytes, dtypes
            # (None for gpt engines)
            "state_cache": self.state_cache_stats(),
            # a described decoder's layers: kinds and counts, its state
            # groups, its router, picks, latent width and experts held
            # (None for gpt engines)
            "decoder": (None if self._desc is None
                        else hybrid_mod.describe(self._desc)),
            # host-DRAM offload tier occupancy and hit/restore counters
            # (None when the tier is off — the inert default)
            "host_kv": self.host_kv_stats(),
            # quantized serving: which of the two int8 modes are live
            # (None when both are off — the inert default)
            "quant": self.quant_info(),
            # sampling mode: per-request params as traced operands
            # (None on greedy-only engines — the inert default)
            "sampling": self.sampling_info(),
            # paged LoRA multiplexing: slot occupancy, refcounts and
            # the loaded-adapter set (None when off — the inert default)
            "adapters": self.adapter_info(),
            "sharding": self.sharding_info(),
            # speculative decoding: k, the draft model's shape/bytes,
            # the rolling acceptance rate and the verify bucket grid
            # (None with spec off)
            "spec": (None if self._spec is None
                     else self._spec.statusz(self)),
            # per-program cost/timing attribution: cost table always
            # (default-on), device-time columns once sampling has run
            # (None with MXTPU_PERF_ATTRIB=0 — the inert default rule)
            "perf": self._perf.statusz(),
            # per-step host-overhead decomposition: ring tail + phase
            # fractions + the perf↔epoch clock anchor timeline_report
            # stitches with ({"enabled": False} with
            # MXTPU_STEP_PROFILE=0 — this knob is default-on)
            "step_profile": self._sprof.statusz(),
            "max_batch": self.max_batch,
            "max_model_len": self.max_model_len,
            "programs_recorded": len(self._manifest.entries()),
            "request_trace": {"enabled": self._rtrace.enabled,
                              "sample": self._rtrace.sample,
                              "traced": self._rtrace.traced,
                              "written": self._rtrace.written,
                              "path": self._rtrace.path},
            "numeric_watch": self._numeric_watch,
            # which paged-attention implementation the decode programs
            # trace ("pallas" | "jnp"): impl="auto" declining the kernel
            # for this cache geometry must be visible, not silent
            "paged_attention": self._paged_impl(),
            # positions the kernel's walk folds a step (None under jnp)
            "paged_tile_tokens": (PAGED_TILE_TOKENS
                                  if self._paged_impl() == "pallas"
                                  else None),
            # the span attention of the prefill and chunk programs
            # ("kernel" | "dense") and the score rectangle (rows x keys)
            # a pass needs for the kernel
            "span_attention": self._span_impl(),
            "span_kernel_min_scores": (SPAN_KERNEL_MIN_SCORES
                                       if self._span_impl() == "kernel"
                                       else None),
            "aot": aot,
        }

    def perf_summary(self):
        """Compact performance-attribution summary — sampled dispatch
        count, MFU/achieved-TFLOP/s, flops-per-token and device cost
        per 1k tokens (None with ``MXTPU_PERF_ATTRIB=0``).  The
        ServeMonitor tail and the fleet replica scrape row read this."""
        return self._perf.summary()

    def adapter_info(self):
        """The ``/statusz`` ``adapters`` section: slot occupancy,
        refcounts and the loaded-adapter ids (None when off — the
        inert default)."""
        if not self._adapters:
            return None
        return self.adapter_store.stats()

    def sampling_info(self):
        """The ``/statusz`` ``sampling`` section: cap, engine defaults
        and the greedy-vs-stochastic spec acceptance split (None on
        greedy-only engines — the inert default)."""
        if not self._sampling:
            return None
        info = {"enabled": True, "sample_cap": self.sample_cap,
                "top_logprobs": TOP_LOGPROBS,
                "defaults": {"temperature": self.temperature,
                             "top_p": self.top_p, "top_k": self.top_k}}
        return info

    def quant_info(self):
        """The ``/statusz`` ``quant`` section: weight-only mode, KV
        dtype, and the byte savings each one buys (None when quantized
        serving is off entirely)."""
        if not self.quantize and not self._kv_quant:
            return None
        info = {"weights": self.quantize,
                "kv_dtype": str(self._cache_k.dtype)
                if self._cache_k is not None else None}
        if self.quantize and self.params:
            info["quantized_weights"] = sum(
                1 for k in self.params if k.endswith("_wscale"))
            info["weight_bytes"] = sum(
                int(v.nbytes) for k, v in self.params.items()
                if k.endswith("_weight") or k.endswith("_wscale"))
        if self._kv_quant and self._scale_k is not None:
            info["kv_scale_bytes"] = 2 * int(self._scale_k.nbytes)
        return info

    def host_block_spec(self):
        """Shapes/dtypes of ONE block's host-copy arrays — the layout
        ``_host_kv_fetch`` produces and the restore program consumes:
        K and V ``(layers, block_size, kv_heads, head_dim)`` in the
        cache dtype, plus the two f32 scale-slot arrays under int8 KV.
        This is the prefill→decode handoff wire decoder's contract: a
        receiving replica validates every record's raw bytes against
        these specs before trusting them."""
        L, bs = self._cfg.n_layers, self.block_size
        Hkv, Dh = self._cfg.kv_heads, self._cfg.head_dim
        dt = np.dtype(str(self._cache_k.dtype))
        specs = [((L, bs, Hkv, Dh), dt), ((L, bs, Hkv, Dh), dt)]
        if self._kv_quant:
            f32 = np.dtype(np.float32)
            specs += [((L, bs, Hkv), f32), ((L, bs, Hkv), f32)]
        return specs

    def host_kv_stats(self):
        """The ``/statusz`` ``host_kv`` section: DRAM budget and
        occupancy, offload/restore/eviction counters and the per-block
        host bytes (None when the tier is off).  The fleet replica's
        load signal reads the same snapshot — a replica whose host tier
        is saturated re-pays recompute on every further eviction."""
        if self._host_pool is None:
            return None
        out = self._host_pool.stats()
        # bytes one parked block costs in DRAM: K + V (+ scale slots)
        per_block = 2 * (self._cache_k.nbytes // self.num_blocks
                         if self._cache_k is not None else 0)
        if self._kv_quant and self._scale_k is not None:
            per_block += 2 * (self._scale_k.nbytes // self.num_blocks)
        out["block_bytes"] = int(per_block)
        return out

    def kv_summary(self):
        """The routable-cache advertisement: the BlockManager's
        ``RadixSummary`` snapshot (counting bloom over every published
        block key in both tiers + the top-K recently published chain
        keys; None with the prefix cache off).  Size-bounded and
        incremental — safe for the fleet replica to publish on every
        ``/healthz``/``/statusz`` scrape at any cache size."""
        return self.blocks.summary()

    def ingest_pulled_blocks(self, records, salt=None):
        """Land a peer-pulled KV chain in the host tier — the engine
        half of the fleet fabric's peer-to-peer pull.  ``records`` is
        the decoded handoff wire shape; ingestion is the SAME
        chain-hash-verified ``import_blocks`` path a prefill→decode
        handoff uses, so a truncated or corrupted pull breaks the
        chain and the suffix recomputes (degradation, never
        corruption).  Returns ``(imported, deduped, rejected)``."""
        self._read_flight()
        return self.blocks.import_blocks(records, salt=salt)

    def sharding_info(self):
        """Live sharding layout: tp degree, mesh shape/devices, rule
        digest, and per-device HBM-resident parameter bytes — the
        /statusz "where do the bytes live" section (replicated arrays
        count once per device, which is exactly their real footprint)."""
        info = {"tp": self.tp,
                "rules_digest": self._rules_digest,
                "spec_digest": self._spec_digest}
        if self.mesh is not None:
            info["mesh"] = {
                "axes": {k: int(v) for k, v in self.mesh.shape.items()},
                "devices": [int(d.id) for d in self.mesh.devices.flat]}
        if self.params:
            info["params_bytes_per_device"] = statusz_mod.bytes_by_device(
                self.params.values())
        return info

    def kv_cache_stats(self):
        """KV-cache memory accounting, global and per chip.  Block
        ACCOUNTING never changes with tp — each chip holds
        ``kv_heads/tp`` of every block, so per-chip bytes (total and
        in-use) drop by the tp degree and the same per-chip HBM budget
        funds ``tp``x the blocks."""
        self._read_flight()
        if self._cache_k is None:
            return None
        total = 2 * int(self._cache_k.nbytes)          # K and V
        per_dev = total // self.tp
        per_block = per_dev // self.num_blocks
        out = {"bytes_total": total,
               "bytes_per_device": per_dev,
               "bytes_per_block_per_device": per_block,
               "bytes_in_use_per_device":
                   per_block * self.blocks.blocks_in_use,
               "dtype": str(self._cache_k.dtype)}
        if self._cache_wk is not None:
            out["window_group_bytes"] = 2 * int(self._cache_wk.nbytes)
        if self._kv_quant:
            # the dequantization scales are real HBM too: the honest
            # per-chip KV footprint is bytes + scale_bytes — an f32
            # scale per head_dim int8 elements, so the reduction is
            # dtype_bytes / (1 + 4/head_dim): at head_dim 64 that is
            # 3.76x from f32 and 1.88x from bf16 (the CPU smoke's
            # 3.56x is f32 at head_dim 32)
            sb = 2 * int(self._scale_k.nbytes)
            out["scale_bytes_total"] = sb
            out["scale_bytes_per_device"] = sb // self.tp
        return out

    def state_cache_stats(self):
        """The ``/statusz`` ``state_cache`` section of a hybrid engine:
        the pool's slots (the null slot excluded), how many admitted
        requests hold one, its bytes and dtypes (None for gpt engines)."""
        self._read_flight()
        if self._state_ssm is None:
            return None
        slots = int(self._state_ssm.shape[1]) - 1
        total = int(self._state_ssm.nbytes) + int(self._state_conv.nbytes)
        return {"slots": slots,
                "in_use": self.blocks.state_slots_in_use,
                "layers": int(self._state_ssm.shape[0]),
                "bytes_total": total,
                "bytes_per_slot": total // (slots + 1),
                "updates_skipped": self._state_skipped,
                "ssm_dtype": str(self._state_ssm.dtype),
                "conv_dtype": str(self._state_conv.dtype)}

    def routed_probe(self):
        """What every routed block of the newest decode pass and of the
        newest prefill or chunk pass took and gave, as the serving
        programs themselves computed it (``serve/hybrid.py``, "probe"):
        ``{"layers": (i, ...), "decode": (u, y), "span": (u, y),
        "decode_rids": [...], "span_rows": (rid, start)}``.  ``u`` and
        ``y`` are host arrays ``(rows, routed layer, d_model)`` in the
        activation dtype: a block's normed input and its output (shared
        expert plus the held experts' part) for the pass's first
        ``max_batch`` rows, both zero for rows that were padding or that
        no pass has written yet.  ``decode_rids[r]`` is the request of the
        decode pass's row ``r`` (its newest position); the span's rows are
        positions ``start ...`` of request ``rid``.  A reference's routed
        block over ``u`` should give ``y``: a check of what was really
        served, with nothing served for the check.  None for an engine
        without a routed block."""
        self._read_flight()
        if self._probe is None:
            return None
        # mxtpu-lint: disable=host-sync (a check's read, outside any step)
        rows = np.asarray(jax.device_get(self._probe))
        out = {"layers": hybrid_mod.probe_layers(self._desc),
               "decode": (rows[hybrid_mod.PROBE_DECODE, :, :, 0],
                          rows[hybrid_mod.PROBE_DECODE, :, :, 1]),
               "span": (rows[hybrid_mod.PROBE_SPAN, :, :, 0],
                        rows[hybrid_mod.PROBE_SPAN, :, :, 1]),
               "decode_rids": list(self._probe_rows["decode"]),
               "span_rows": self._probe_rows["span"]}
        if self._diffusion:
            # a denoise pass's probe rows are its rows' block positions
            blocks = out["decode_rids"]
            out["decode_blocks"] = blocks
            out["decode_rids"] = [rid for rid, _, _ in blocks
                                  for _ in range(self._block)]
        return out

    def shutdown(self):
        """Cancel in-flight work and release the device cache.

        Device buffers this engine materialized — sharded or
        replicated parameter placements and the KV cache — are deleted
        explicitly (not left to GC), so constructing engines
        back-to-back in one process can never transiently hold two
        models' HBM.  Arrays the caller passed in that were adopted
        as-is are never touched."""
        if not self._alive:
            return
        try:
            # the tokens of a pass already enqueued are its requests'
            self._read_flight()
        except Exception:
            # a device that fell over: its requests are cancelled below
            flight_mod.recorder().record(
                "error", site="engine.shutdown",
                error=traceback.format_exc(limit=4))
        self._flight.clear()
        for req in (list(self.scheduler.running)
                    + list(self.scheduler.prefilling)):
            self.scheduler.finish(req, status=CANCELLED)
        for req in self.scheduler.drain_waiting():
            req.status = CANCELLED
            req.finish_t = self.clock()
            self._rtrace.terminal(req, CANCELLED)
        with self._fanout_lock:
            pending, self._pending_fanout = self._pending_fanout, []
        for _, sibs in pending:
            # n>1 siblings still engine-side (their primary never
            # finished prefill) resolve like drained waiters
            for s in sibs:
                s.status = CANCELLED
                s.finish_t = self.clock()
                self._rtrace.terminal(s, CANCELLED)
        self._rtrace.close()
        statusz_mod.unregister(self._statusz_name)
        if self._spec is not None:
            self._spec.shutdown()
            self._spec = None
        for arr in (self._owned + [self._cache_k, self._cache_v]
                    + ([self._scale_k, self._scale_v]
                       if self._scale_k is not None else [])
                    + [getattr(self, a) for a in self._extra_caches]
                    + ([] if self._tok_pool is None
                       else [self._tok_pool])):
            try:
                arr.delete()
            except (RuntimeError, ValueError):
                pass              # already donated-away or deleted
        self._owned = []
        self._cache_k = self._cache_v = None
        self._scale_k = self._scale_v = None
        self._state_ssm = self._state_conv = None
        self._cache_wk = self._cache_wv = self._probe = None
        self._tok_pool = None
        self._tok_fns = {}
        if self._host_pool is not None:
            # the DRAM tier releases WITH the device buffers: two
            # engines back-to-back must never transiently hold two
            # host pools' worth of parked K/V either
            self._host_pool.clear()
            self._host_pool = None
        self.params = None            # free the device-resident weights
        self._alive = False

    # -- execution -----------------------------------------------------------
    def _req_sampling_operands(self, req):
        """(1,)-shaped per-request sampling operands for the prefill
        and chunk programs (empty on greedy-only engines — their
        program signatures are the historical ones)."""
        if not self._sampling:
            return ()
        return (jnp.asarray([req.temperature], jnp.float32),
                jnp.asarray([req.top_p], jnp.float32),
                jnp.asarray([req.top_k or 0], jnp.int32))

    def _batch_sampling_operands(self, reqs, bucket):
        """(B,)-shaped per-SLOT sampling operands for the decode /
        draft / verify programs — THE tentpole mechanism: temperature,
        top-p and top-k ride the batch as data, so one bucketed
        program serves any mix of sampling configs with zero fresh
        traces (padding rows are greedy — harmless, their outputs are
        dropped)."""
        if not self._sampling:
            return ()
        temp = np.zeros(bucket, np.float32)
        topp = np.ones(bucket, np.float32)
        topk = np.zeros(bucket, np.int32)
        for i, req in enumerate(reqs):
            temp[i] = req.temperature
            topp[i] = req.top_p
            topk[i] = req.top_k or 0
        return (jnp.asarray(temp), jnp.asarray(topp), jnp.asarray(topk))

    def _adapter_args(self):
        """The LoRA device-stack operand every target-model program
        takes right after the params (empty on adapters-off engines —
        their program signatures are the historical ones)."""
        if not self._adapters:
            return ()
        return (self.adapter_store.device,)

    def _req_adapter_operand(self, req):
        """Scalar adapter-slot operand for the prefill/chunk programs
        (empty when off)."""
        if not self._adapters:
            return ()
        return (jnp.asarray(req.adapter_slot, jnp.int32),)

    def _batch_adapter_operands(self, reqs, bucket):
        """(B,)-shaped per-slot adapter indices for the decode/verify
        programs — the second traced-operand family after sampling:
        each row gathers its own A/B slices, so one bucketed program
        serves any adapter mix (padding and base rows are slot 0, the
        true zero delta)."""
        if not self._adapters:
            return ()
        slots = np.zeros(bucket, np.int32)
        for i, req in enumerate(reqs):
            slots[i] = req.adapter_slot
        return (jnp.asarray(slots),)

    def _req_state_operand(self, req):
        """Scalar state-slot operand of a hybrid engine's prefill and
        chunk programs (empty for gpt engines)."""
        if self._state_ssm is None:
            return ()
        return (jnp.asarray(self.blocks.state_slot(req.rid), jnp.int32),)

    def _batch_state_operands(self, reqs, bucket):
        """What a described decoder's decode program takes behind the
        block tables: the rows' state slots ``(B,)`` where it has a state
        pool (padded rows name the null slot 0), then the rows' window-
        group tables where it has a window group."""
        out = ()
        if self._state_ssm is not None:
            slots = np.zeros(bucket, np.int32)
            for i, req in enumerate(reqs):
                slots[i] = self.blocks.state_slot(req.rid)
            out += (jnp.asarray(slots),)
        if self.blocks.window is not None:
            tables = np.zeros((bucket, self.table_width), np.int32)
            for i, req in enumerate(reqs):
                self.blocks.window_table(req.rid, tables[i])
            out += (jnp.asarray(tables),)
        return out

    def _window_operands(self, req, start, end, bucket, table):
        """The window group's operands of a prefill or
        chunk pass over positions ``[start, end)``.  ``table``: a chunk
        pass, which writes its whole span through the group's table and
        then attends through it; the operands are the table and the write
        blocks.  Else a whole prompt, which attends to its own rows: the
        group holds only what a query at ``end`` still sees, the rows
        behind that (and every bucket's padding) write to the null block,
        and the operand is the write blocks.  Empty for an engine
        without the group."""
        if self.blocks.window is None:
            return ()
        tw = np.zeros(self.table_width, np.int32)
        lo = start if table else max(0, end - self._desc.window + 1)
        self.blocks.window_cover(req.rid, lo, end)
        self.blocks.window_table(req.rid, tw)
        blk = np.zeros(bucket, np.int32)
        pos = np.arange(start, end)
        blk[:end - start] = np.where(pos >= lo, tw[pos // self.block_size],
                                     0)
        return ((jnp.asarray(tw),) if table else ()) + (jnp.asarray(blk),)

    def _note_moe(self, p, stats):
        """A pass's router counts (``ops.moe.STATS``) onto its span and
        into pass ``p``'s sum."""
        # mxtpu-lint: disable=host-sync (host numpy already: the counts
        # arrived in _unpack_outs's batched read, with the tokens)
        stats = np.asarray(stats, np.int64)
        p.moe = stats if p.moe is None else p.moe + stats
        if self._sprof.tracing:
            self._sprof.note(**{k: int(v) for k, v in
                                zip(moe_ops.STATS, stats)})

    def _note_logprobs(self, req, chosen, tv, ti):
        """Record emitted tokens' logprob outputs on the request: the
        chosen-token logprob always (sampling mode), the top view
        trimmed to the request's ``logprobs`` ask."""
        for j in range(len(chosen)):
            # mxtpu-lint: disable=host-sync (host numpy already: the
            # logprob views arrived in _unpack_outs's batched read)
            req.token_logprobs.append(float(chosen[j]))
            if req.logprobs:
                req.top_logprobs.append(
                    # mxtpu-lint: disable=host-sync (host numpy
                    # already — same batched read as above)
                    [[int(t), float(v)]
                     for t, v in zip(ti[j][:req.logprobs],
                                     tv[j][:req.logprobs])])

    def _adopt(self, outs, n_lead):
        """Split a program's output tuple as it is enqueued: adopt the
        donated-through caches (the next program's operands) and return
        the ``n_lead`` host-bound outputs (sampled tokens, and in
        sampling mode the logprob views), still on the device, with the
        numeric watchdog's logits-finite flag (None without it)."""
        ok = None
        if self._cfg.numeric_watch:
            ok, n_caches = outs[n_lead], n_lead + 1
        else:
            n_caches = n_lead
        self._set_caches(outs[n_caches:])
        return tuple(outs[:n_lead]), ok

    def _unpack_outs(self, lead, ok, anomaly, **fields):
        """Bring a program's host-bound outputs (:meth:`_adopt`) to the
        host in ONE batched read, and fire the numeric-watchdog anomaly
        when the logits-finite flag rode along false."""
        if ok is not None:
            # one batched read: the sampled tokens must reach the host
            # anyway, so the watchdog flag rides the same sync instead
            # of forcing a second one
            # mxtpu-lint: disable=host-sync (designed sync point: the
            # scheduler needs the sampled tokens on the host)
            got = jax.device_get(lead + (ok,))
            if not got[-1]:
                flight_mod.record_anomaly(anomaly, step=self._step_id,
                                          **fields)
            return got[:-1]
        # mxtpu-lint: disable=host-sync (designed sync point: the
        # scheduler needs the sampled tokens on the host)
        return jax.device_get(lead)

    def _token_program(self, kind, n=0):
        """One of the token pool's small programs (``serve/programs.py``),
        compiled for a decode bucket of ``n`` rows (``TOK_PUT1``: one
        prefill's token, whatever its bucket).  Made ready by ``warmup``
        with the decode, prefill and chunk entries they serve; they are
        no manifest kind of their own."""
        fn = self._tok_fns.get((kind, n))
        if fn is not None:
            return fn
        key = (self._spec_key(), kind, n, self._tok_pool.shape)
        fn = _STEP_CACHE.get(key)
        if fn is None:
            i32 = jnp.dtype(jnp.int32)
            rep = None if self._shardings is None else self._shardings.rep
            sds = lambda shape: jax.ShapeDtypeStruct(shape, i32,
                                                     sharding=rep)
            pool = sds(self._tok_pool.shape)
            # a row's tokens: one, or a diffusion decoder's block
            row = (n,) + self._tok_pool.shape[1:]
            specs = {TOK_TAKE: (pool, sds(row), sds((n,))),
                     TOK_PUT: (pool, sds(row)),
                     TOK_PUT1: (pool, sds(()), sds(()))}[kind]
            with telemetry.span("serve.token_program", kind=kind,
                                bucket=int(n)):
                fn = _build_token_program(kind, self._shardings).lower(
                    *specs).compile()
            _STEP_CACHE[key] = fn
        self._tok_fns[kind, n] = fn
        return fn

    def _n_lead(self, decode=False):
        """Host-bound outputs of a prefill, chunk or decode program: the
        sampled token (with its logprob views in sampling mode), or a
        denoise pass's blocks and unmasked positions, and, where a
        feed-forward block is routed, the router's counts."""
        if decode and self._diffusion:
            return 2 + int(self._routed)
        return (4 if self._sampling else 1) + int(self._routed)

    def _cache_args(self):
        """The device cache operands every target-model program takes:
        (k, v) — plus the int8-KV scale pair when quantized (the same
        order the program builders and ``_program_specs`` use)."""
        if self._kv_quant:
            return (self._cache_k, self._cache_v,
                    self._scale_k, self._scale_v)
        # then what a description brings (serve/hybrid.py::extra_caches)
        return (self._cache_k, self._cache_v) + tuple(
            getattr(self, a) for a in self._extra_caches)

    def _set_caches(self, arrs):
        """Adopt a program's returned (donated-through) cache operands
        — the tail of its output tuple, mirroring :meth:`_cache_args`."""
        if self._kv_quant:
            (self._cache_k, self._cache_v,
             self._scale_k, self._scale_v) = arrs
        else:
            self._cache_k, self._cache_v, *extra = arrs
            for a, arr in zip(self._extra_caches, extra, strict=True):
                setattr(self, a, arr)

    def _host_kv_fetch(self, blk):
        """Device→host copy of ONE block's K/V (and int8 scale slots)
        for the offload tier — called by the BlockManager's prefix-LRU
        eviction just before the device block is recycled.  The copies
        start asynchronously and the sync covers one block only (tens
        of KB), a bounded, designed cost on the eviction path; under tp
        the gather round-trips each chip's head shard into one full
        host block."""
        if self._cache_k is None:
            return None
        parts = [self._cache_k[:, blk], self._cache_v[:, blk]]
        if self._kv_quant:
            parts += [self._scale_k[:, blk], self._scale_v[:, blk]]
        for a in parts:
            start = getattr(a, "copy_to_host_async", None)
            if start is not None:
                start()
        # mxtpu-lint: disable=host-sync (designed sync point: the
        # evicted block's bytes must reach DRAM before its device
        # buffer is reused — one small bounded copy per eviction)
        return tuple(np.asarray(a) for a in parts)

    @hot_path
    def _restore_pending(self):
        """Dispatch the queued host→device restores as ONE bucketed
        ``restore`` program per batch: the copies ride the async
        dispatch stream AHEAD of this iteration's prefill/decode
        programs, so the cache dataflow (the restored arrays feed the
        next program's cache operands) fences them before the first
        read and the step loop never blocks on a copy."""
        pending = self.blocks.take_pending_restores()
        if not pending:
            return
        L, bs = self._cfg.n_layers, self.block_size
        Hkv, Dh = self._cfg.kv_heads, self._cfg.head_dim
        cap = self.table_width
        while pending:
            batch, pending = pending[:cap], pending[cap:]
            bucket = _next_bucket(len(batch), cap)
            blks = np.zeros(bucket, np.int32)   # pad rows -> null block
            hk = np.zeros((L, bucket, bs, Hkv, Dh), self._cache_k.dtype)
            hv = np.zeros_like(hk)
            if self._kv_quant:
                hks = np.zeros((L, bucket, bs, Hkv), np.float32)
                hvs = np.zeros_like(hks)
            for i, (blk, arrays) in enumerate(batch):
                blks[i] = blk
                hk[:, i] = arrays[0]
                hv[:, i] = arrays[1]
                if self._kv_quant:
                    hks[:, i] = arrays[2]
                    hvs[:, i] = arrays[3]
            args = self._cache_args() + (jnp.asarray(blks),
                                         jnp.asarray(hk),
                                         jnp.asarray(hv))
            if self._kv_quant:
                args += (jnp.asarray(hks), jnp.asarray(hvs))
            with telemetry.span("serve.host_kv_restore",
                                blocks=len(batch)):
                outs = self._program("restore", bucket)(*args)
                self._perf.done("restore", bucket)
                self._set_caches(outs)

    def _slots(self, table, n, pad_to):
        """(block, offset) scatter targets for logical slots [0, n),
        padded to ``pad_to`` with null-block writes."""
        blk = np.zeros(pad_to, np.int32)
        off = np.arange(pad_to, dtype=np.int32) % self.block_size
        pos = np.arange(n)
        # mxtpu-lint: disable=host-sync (block tables are host lists —
        # pure host-side scatter-target math, no device values)
        blk[:n] = np.asarray(table, np.int32)[pos // self.block_size]
        return blk, off

    @hot_path
    def _run_prefill(self, req, decode_slots=0):
        """Enqueue one prefill pass for ``req``: the whole uncached suffix
        (cold path, or a prefix-cache hit's remainder), or — when the
        scheduler put it in the chunked-prefill lane — ONE budget-sized
        chunk.  Returns the unread :class:`_Part`; :meth:`_read_prefill`
        reads it.  What the NEXT schedule needs of the pass is done here,
        from what the host knows already: the request's positions in
        flight, its place in the decode batch after the last chunk, the
        prompt's blocks published (their ids are the host's; the K/V is
        in the queue ahead of any reader's program), the window group
        trimmed."""
        ids = req.prefill_ids()
        # the positions this prefill writes: all of ids, or under block
        # diffusion their whole blocks (the rest opens the first block)
        n = int(req.prefill_len())
        start = int(req.next_pos())    # cached prefix + earlier chunks
        resume = req.n_preemptions > 0
        chunked = self.scheduler.is_prefilling(req)
        if chunked:
            # whole blocks under block diffusion, at least one
            budget = max(self._block,
                         self.scheduler.prefill_chunk - decode_slots)
            end = min(n, start + budget - budget % self._block)
        else:
            end = n
        if not req._prefill_started:
            req._prefill_started = True
            self._rtrace.event(req, "prefill_start", tokens=int(n - start),
                               cached=start, chunked=chunked,
                               resume=resume)
        span = end - start
        self._key, sub = jax.random.split(self._key)
        if start == 0 and end == n:
            # cold whole-prompt pass: the dense O(n^2)-attention
            # program (exactly the pre-prefix-cache path)
            bucket = _next_bucket(n, self.max_model_len)
            toks = np.zeros(bucket, np.int32)
            toks[:n] = ids[:n]
            blk, off = self._slots(self.blocks.table(req.rid), n, bucket)
            pkind, keys = "prefill", bucket
            fn = self._prefill_fn(bucket)
            args = (self.params,) + self._adapter_args() \
                + self._cache_args() + (
                    jnp.asarray(toks), jnp.asarray(n, jnp.int32),
                    jnp.asarray(blk), jnp.asarray(off)) \
                + self._req_adapter_operand(req) \
                + self._req_state_operand(req) \
                + self._window_operands(req, 0, n, bucket, table=False) \
                + self._req_sampling_operands(req) + (sub,)
        else:
            # suffix/chunk pass: positions [start, end) attend through
            # the block table to the K/V already in the cache (cached
            # prefix + earlier chunks) — cached positions are never
            # recomputed and shared blocks are never written
            bucket = _next_bucket(span, self._chunk_cap())
            toks = np.zeros(bucket, np.int32)
            toks[:span] = ids[start:end]
            table = self.blocks.table(req.rid)
            tw = np.zeros(self.table_width, np.int32)
            tw[:len(table)] = table
            pos = start + np.arange(span)
            blk = np.zeros(bucket, np.int32)   # padded rows -> null blk
            blk[:span] = tw[pos // self.block_size]
            off = ((start + np.arange(bucket))
                   % self.block_size).astype(np.int32)
            pkind, keys = "chunk", self.table_width * self.block_size
            fn = self._chunk_fn(bucket)
            args = (self.params,) + self._adapter_args() \
                + self._cache_args() + (
                    jnp.asarray(toks), jnp.asarray(start, jnp.int32),
                    jnp.asarray(span, jnp.int32), jnp.asarray(tw),
                    jnp.asarray(blk), jnp.asarray(off)) \
                + self._req_adapter_operand(req) \
                + self._req_state_operand(req) \
                + self._window_operands(req, start, end, bucket,
                                        table=True) \
                + self._req_sampling_operands(req) + (sub,)
        note = {}
        if self._state_ssm is not None:
            # a pass from position 0 starts the slot's state from zero
            # inside the program: at admission, and again when a
            # preempted request is prefilled anew
            note["state"] = ("carried" if start else
                             "reset" if resume else "fresh")
            if not start:
                self._tel_state_resets.labels(
                    reason="preempt" if resume else "admit").inc()
        if self._sprof.tracing:
            # which span attention the pass runs, and the key tiles one
            # head of it computes over those of its (rows, keys) rectangle
            attn = self._span_impl(bucket, keys)
            tiles, of = span_kv_tiles(bucket, keys, start, span,
                                      self.window, attn, block=self._block)
            note.update(kind=pkind, tokens=span, bucket=bucket, start=start,
                        cached=req.cached_prefix_len, attn=attn,
                        kv_tiles=tiles, kv_tiles_table=of)
        outs = fn(*args)
        first = self._perf.done(pkind, bucket)
        lead, ok = self._adopt(outs, self._n_lead())
        part = _Part(req, lead, ok, note, start, end, n)
        req.flight_len += span
        if self.blocks.window is not None:
            part.freed = self.blocks.window_trim(req.rid, end)
        # publish the newly-FULL blocks under their chain keys so later
        # prompts (or this request's own post-preemption resume) can
        # reuse them — host-side dict work only
        # the request's adapter id salts the chain: adapter K/V is
        # content-disjoint from base (and other-adapter) K/V
        self.blocks.note_tokens(req.rid, ids[:end], salt=req.adapter_id)
        if end >= n and self._diffusion:
            # the prompt's last n mod B tokens and mask ids open the
            # first block, which the host knows: no token in flight
            req.diffusion = _Block(self._desc, ids[n:], opens=True)
            # a preempted request's block starts over: its record keeps
            # the committed blocks only
            req.unmasked = [u for u in req.unmasked if u[0] < n]
            self.scheduler.prefill_done(req)
            self.scheduler.admit_running(req)
        elif end >= n:
            # the sampled token is the request's next: it joins the
            # decode batch of the next schedule, its token on the device
            # until this pass is read
            req.flight_tokens += 1
            if self._tok_pool is not None:
                # the rows behind the decode's, in turn: two passes'
                # worth, so this pass's prefills leave alone what the
                # pass before put there for this pass's decode to take
                self._tok_row = (self._tok_row + 1) % (
                    self._tok_pool.shape[0] - self.max_batch)
                req.flight_src = self.max_batch + self._tok_row
                self._tok_pool = self._token_program(TOK_PUT1)(
                    self._tok_pool, lead[0],
                    jnp.asarray(req.flight_src, jnp.int32))
            self.scheduler.prefill_done(req)
            self.scheduler.admit_running(req)
        if self._sprof.tracing:
            part.rec = self._sprof.enqueued(pkind, bucket, span, lead[0],
                                            first)
        return part

    @hot_path
    def _read_prefill(self, p, part):
        """Read one prefill pass of pass ``p``.  Returns the tokens
        emitted (1 on the pass that samples the first token, 0 for an
        intermediate chunk)."""
        req, sprof = part.reqs, self._sprof
        sprof.wait("serve.prefill", part.rec, rid=req.rid, **part.args)
        lead = self._unpack_outs(part.lead, part.ok, "prefill_logits",
                                 rid=req.rid)
        sprof.enter("host_sync")
        start, end, n = part.start, part.end, part.n
        span = end - start
        resume = req.n_preemptions > 0
        tok = lead[0]
        if self._routed:
            self._note_moe(p, lead[-1])
            self._probe_rows["span"] = (req.rid, start)
        p.freed += part.freed
        req.flight_len -= span
        req.prefill_passes += 1
        req.cache_len = end
        self._stats.on_prefill(span)
        if end < n:
            # intermediate chunk: the sampled token is bogus (mid-
            # prompt) and dropped; the request stays in the prefilling
            # lane and owns the next iteration's prefill budget
            self._rtrace.event(req, "prefill_chunk", done=int(end),
                               target=int(n), tokens=int(span))
            return 0
        if self._diffusion:
            # the prompt's whole blocks are in; its first tokens come
            # with the first block's store pass (_read_denoise)
            self._rtrace.event(req, "prefill_end", tokens=int(n - start),
                               resume=resume)
            return 0
        req.flight_tokens -= 1
        self._rtrace.event(req, "prefill_end", tokens=int(n - start),
                           resume=resume)
        now = self.clock()
        if req.first_token_t is None:
            req.first_token_t = now
            self._stats.on_first_token(req.ttft() or 0.0)
        else:
            # resume prefill after preemption: the re-emitted token's
            # gap (spanning the preempted wait) IS the client-visible
            # inter-token latency — it belongs in the TPOT tail
            self._stats.on_tokens(req, 1, now=now)
        if sprof.tracing:
            # admission to first token: with serve.request.queued (the
            # scheduler's) it splits a request's TTFT, joined by rid
            telemetry.tracer().add_complete(
                "serve.request.prefill", req.admit_t, now,
                {"rid": req.rid, "resume": int(resume),
                 "passes": req.prefill_passes,
                 "tokens": n - req.cached_prefix_len})
        req.tokens.append(int(tok))
        if self._sampling:
            self._note_logprobs(req, [lead[1]], [lead[2]], [lead[3]])
        self._maybe_finish(req)
        return 1

    @hot_path
    def _run_decode(self, reqs):
        """Enqueue the batched decode of ``reqs``; returns the unread
        :class:`_Part` (:meth:`_read_decode` reads it).  A row whose
        newest token the host has not read takes it from the token pool,
        on the device; a batch that needs none of those gets its tokens
        from the host, through the same program."""
        if self._diffusion:
            return self._run_denoise(reqs)
        B = len(reqs)
        bucket = _next_bucket(B, self.max_batch)
        note = {}
        if self._sprof.tracing:
            note = dict(batch=B, bucket=bucket,
                        **self._kv_tiles(reqs, bucket))
        if self._state_ssm is not None:
            # the padded rows name the null slot: the state and the
            # convolution kernels do no work for them
            # (ops/pallas_ssm_update.py, ops/pallas_conv_step.py)
            skipped = (bucket - B) * int(self._state_ssm.shape[0])
            self._state_skipped += skipped
            self._tel_state_skipped.inc(skipped)
            if self._sprof.tracing:
                note["state_rows_skipped"] = bucket - B
        toks = np.zeros(bucket, np.int32)
        pos = np.zeros(bucket, np.int32)
        tables = np.zeros((bucket, self.table_width), np.int32)
        src = None
        for i, req in enumerate(reqs):
            if req.flight_tokens:
                if src is None:
                    src = np.full(bucket, -1, np.int32)
                src[i] = req.flight_src
            else:
                toks[i] = req.tokens[-1]
            pos[i] = req.next_pos()
            t = self.blocks.table(req.rid)
            tables[i, :len(t)] = t
        toks = jnp.asarray(toks)
        if src is not None:
            toks = self._token_program(TOK_TAKE, bucket)(
                self._tok_pool, toks, jnp.asarray(src))
        fn = self._decode_fn(bucket)
        self._key, sub = jax.random.split(self._key)
        outs = fn(self.params, *self._adapter_args(),
                  *self._cache_args(),
                  toks, jnp.asarray(pos),
                  jnp.asarray(tables),
                  *self._batch_adapter_operands(reqs, bucket),
                  *self._batch_state_operands(reqs, bucket),
                  *self._batch_sampling_operands(reqs, bucket), sub)
        first = self._perf.done("decode", bucket)
        lead, ok = self._adopt(outs, self._n_lead())
        part = _Part(reqs, lead, ok, note)
        self._tok_pool = self._token_program(TOK_PUT, bucket)(
            self._tok_pool, lead[0])
        if self._sprof.tracing:
            part.rec = self._sprof.enqueued("decode", bucket, B, lead[0],
                                            first)
        trim = self.blocks.window is not None
        for i, req in enumerate(reqs):
            req.flight_len += 1
            req.flight_tokens += 1
            req.flight_src = i
            if trim:
                part.freed += self.blocks.window_trim(req.rid,
                                                      req.next_pos())
        return part

    @hot_path
    def _run_denoise(self, reqs):
        """Enqueue a diffusion decoder's decode pass over ``reqs``: each
        row's block at its committed length, a denoise step of its
        static schedule or, with no mask left, the store pass (count 0).
        A row whose block the unread pass is denoising takes it from the
        token pool, on the device; the host foresees every row's kind
        and position (``_Block``).  Returns the unread :class:`_Part`."""
        R, B = len(reqs), self._block
        bucket = _next_bucket(R, self.max_batch)
        toks = np.zeros((bucket, B), np.int32)
        pos = np.zeros(bucket, np.int32)
        counts = np.zeros(bucket, np.int32)
        tables = np.zeros((bucket, self.table_width), np.int32)
        src, rows = None, []
        for i, req in enumerate(reqs):
            blk = req.diffusion
            if blk.host is None:
                if src is None:
                    src = np.full(bucket, -1, np.int32)
                src[i] = req.flight_src
            else:
                toks[i] = blk.host
            pos[i] = req.next_pos()
            counts[i] = blk.next_count()
            t = self.blocks.table(req.rid)
            tables[i, :len(t)] = t
            rows.append(blk.launch())
        steps = self._desc.steps
        at_step = np.bincount([row[1] for row in rows],
                              minlength=steps + 1)
        n_store = int(at_step[steps])
        note = {}
        if self._sprof.tracing:
            # block_step: the rows at each step of their blocks, 0 ..
            # steps - 1 denoising, ``steps`` storing; contexts: the
            # positions every row's paged query attends over
            note = dict(batch=R, bucket=bucket,
                        block_step=",".join(str(int(n)) for n in at_step),
                        unmasked=int(counts.sum()), committed=n_store,
                        contexts=int(pos[:R].sum()) + R * B,
                        **self._kv_tiles(reqs, bucket))
        self._tel_block_passes.labels(kind="denoise").inc(R - n_store)
        self._tel_block_passes.labels(kind="store").inc(n_store)
        toks = jnp.asarray(toks)
        if src is not None:
            toks = self._token_program(TOK_TAKE, bucket)(
                self._tok_pool, toks, jnp.asarray(src))
        fn = self._decode_fn(bucket)
        self._key, sub = jax.random.split(self._key)
        outs = fn(self.params, *self._cache_args(), toks, jnp.asarray(pos),
                  jnp.asarray(tables), jnp.asarray(counts), sub)
        first = self._perf.done("decode", bucket)
        lead, ok = self._adopt(outs, self._n_lead(decode=True))
        part = _Part(reqs, lead, ok, note)
        part.blocks = rows
        self._tok_pool = self._token_program(TOK_PUT, bucket)(
            self._tok_pool, lead[0])
        if self._sprof.tracing:
            part.rec = self._sprof.enqueued("decode", bucket, R, lead[0],
                                            first)
        for i, (req, (blk, step, fixed)) in enumerate(zip(reqs, rows)):
            if step == blk.steps:
                # the store pass commits the block: its positions and
                # its new tokens, the last block's tail dropped
                req.flight_len += B
                req.flight_tokens += min(
                    B - fixed,
                    req.max_new_tokens - len(req.tokens) - req.flight_tokens)
                req.diffusion = _Block(self._desc, ())
            else:
                req.flight_src = i
        return part

    @hot_path
    def _read_denoise(self, p, part):
        """Read a diffusion decoder's decode pass: each row's new block
        and which positions it unmasked; a store pass's row commits its
        block (``cache_len`` grows by B in the step its tokens are
        appended).  Returns the tokens emitted."""
        reqs, B = part.reqs, self._block
        self._sprof.wait("serve.decode", part.rec, **part.args)
        lead = self._unpack_outs(part.lead, part.ok, "decode_logits",
                                 batch_size=len(reqs),
                                 rids=[r.rid for r in reqs])
        self._sprof.enter("host_sync")
        out, fresh = lead[0], lead[1]
        now = self.clock()
        if self._routed:
            self._note_moe(p, lead[-1])
            self._probe_rows["decode"] = [
                (r.rid, int(r.cache_len),
                 [int(t) for t in np.where(fresh[i], self._desc.mask_id,
                                           out[i])])
                for i, r in enumerate(reqs)]
        emitted = unmasked = committed = 0
        for i, (req, (blk, step, fixed)) in enumerate(zip(reqs,
                                                          part.blocks)):
            base = req.cache_len
            if step < blk.steps:
                blk.read(out[i], step)
                for b in np.flatnonzero(fresh[i]):
                    req.unmasked.append((base + int(b), int(out[i][b]),
                                         step))
                    unmasked += 1
                continue
            k = min(B - fixed, req.max_new_tokens - len(req.tokens))
            if blk.opens and self._sprof.tracing:
                # admission to the first tokens since it, as a gpt
                # engine's prefill pass records it
                telemetry.tracer().add_complete(
                    "serve.request.prefill", req.admit_t, now,
                    {"rid": req.rid, "resume": int(req.n_preemptions > 0),
                     "passes": req.prefill_passes,
                     "tokens": req.cache_len - req.cached_prefix_len})
            req.flight_len -= B
            req.flight_tokens -= k
            req.cache_len += B
            req.tokens.extend(int(t) for t in out[i][fixed:fixed + k])
            committed += 1
            emitted += k
            if req.first_token_t is None:
                req.first_token_t = now
                self._stats.on_first_token(req.ttft() or 0.0)
            else:
                self._stats.on_tokens(req, k, now=now)
            self._rtrace.event(req, "decode", batch=self._step_id,
                               batch_size=len(reqs), tokens=len(req.tokens),
                               emitted=k)
            self._maybe_finish(req)
        self._tel_unmasked.inc(unmasked)
        self._tel_committed.inc(committed)
        return emitted

    @hot_path
    def _read_decode(self, p, part):
        if self._diffusion:
            return self._read_denoise(p, part)
        reqs = part.reqs
        B = len(reqs)
        self._sprof.wait("serve.decode", part.rec, **part.args)
        lead = self._unpack_outs(part.lead, part.ok, "decode_logits",
                                 batch_size=B, rids=[r.rid for r in reqs])
        self._sprof.enter("host_sync")
        out = lead[0]
        now = self.clock()
        if self._routed:
            self._note_moe(p, lead[-1])
            self._probe_rows["decode"] = [r.rid for r in reqs]
        p.freed += part.freed
        for i, req in enumerate(reqs):
            req.flight_len -= 1
            req.flight_tokens -= 1
            req.cache_len += 1
            req.tokens.append(int(out[i]))
            if self._sampling:
                self._note_logprobs(req, lead[1][i:i + 1],
                                    lead[2][i:i + 1], lead[3][i:i + 1])
            self._stats.on_tokens(req, 1, now=now)
            self._rtrace.event(req, "decode", batch=self._step_id,
                               batch_size=B, tokens=len(req.tokens),
                               emitted=1)
            self._maybe_finish(req)
        return B

    def _spec_ingest(self, req):
        """Bring the draft cache up to date with ``req``'s context —
        positions ``[0, cache_len)`` run through the draft model's
        chunk program in one dispatch.  Needed at admission and after
        a preemption-resume (the draft side re-ingests into the new
        block table; a prefix-cache hit's shared blocks are simply
        rewritten with recomputed values, which can only perturb the
        ACCEPTANCE rate of other sharers, never any emitted token)."""
        span = self._spec.context_gap(req)
        if span <= 0:
            return
        ids = req.prefill_ids()[:span]
        bucket = _next_bucket(span, self.max_model_len)
        toks = np.zeros(bucket, np.int32)
        toks[:span] = ids
        table = self.blocks.table(req.rid)
        tw = np.zeros(self.table_width, np.int32)
        tw[:len(table)] = table
        pos = np.arange(span)
        blk = np.zeros(bucket, np.int32)       # padded rows -> null blk
        blk[:span] = tw[pos // self.block_size]
        off = (np.arange(bucket) % self.block_size).astype(np.int32)
        self._key, sub = jax.random.split(self._key)
        sw = self._spec
        with telemetry.span("serve.spec_ingest", rid=req.rid,
                            tokens=span):
            # the chunk program built over the DRAFT config: same
            # write-then-attend body, draft params and draft caches
            outs = self._program("draft_chunk", bucket)(
                sw.params, sw.cache_k, sw.cache_v,
                jnp.asarray(toks), jnp.asarray(0, jnp.int32),
                jnp.asarray(span, jnp.int32), jnp.asarray(tw),
                jnp.asarray(blk), jnp.asarray(off), sub)
            self._perf.done("draft_chunk", bucket)
            _, sw.cache_k, sw.cache_v = outs
        sw.note_ingested(req, span)

    @hot_path
    def _run_spec_decode(self, reqs):
        """One speculative decode iteration over the batch: one draft
        dispatch proposes ``spec_k`` tokens per request, one verify
        dispatch scores all ``k+1`` positions through the block tables,
        and acceptance emits between 1 and ``k+1`` tokens per request.

        Greedy engines use exact argmax-prefix acceptance (host-side
        ``accept_greedy`` — byte-identical to plain decode).  Sampling
        engines use REJECTION-SAMPLING acceptance (Leviathan/Chen
        2023), entirely on device: the draft SAMPLES each proposal
        from its warped distribution q and ships q with the tokens
        (device-to-device), the verify accepts draft j with prob
        ``min(1, p/q)`` and resamples the first rejection from the
        normalized residual ``max(p - q, 0)`` — the emitted stream is
        distribution-identical to plain sampling from p, whatever the
        draft proposes (greedy rows degenerate to argmax-prefix
        acceptance exactly: p and q are one-hot there)."""
        B = len(reqs)
        k = self.spec_k
        sw = self._spec
        for req in reqs:
            self._spec_ingest(req)
        bucket = _next_bucket(B, self.max_batch)
        note = {}
        if self._sprof.tracing:
            note = dict(batch=B, bucket=bucket,
                        **self._kv_tiles(reqs, bucket))
        toks = np.zeros(bucket, np.int32)
        pos = np.zeros(bucket, np.int32)
        tables = np.zeros((bucket, self.table_width), np.int32)
        for i, req in enumerate(reqs):
            toks[i] = req.tokens[-1]
            pos[i] = req.cache_len
            t = self.blocks.table(req.rid)
            tables[i, :len(t)] = t
        jp, jtab = jnp.asarray(pos), jnp.asarray(tables)
        self._key, sub = jax.random.split(self._key)
        if self._sampling:
            samp = self._batch_sampling_operands(reqs, bucket)
            douts = self._draft_fn(bucket)(
                sw.params, sw.cache_k, sw.cache_v,
                jnp.asarray(toks), jp, jtab, *samp, sub)
            first = self._perf.done("draft", bucket)
            drafted, q_at, q_vals, q_idx, sw.cache_k, sw.cache_v = douts
            # drafted ids and their candidate-space q views stay ON
            # DEVICE: acceptance runs inside the verify program, so
            # the only host sync this iteration is the emitted rows
            fn = self._verify_fn(bucket)
            self._key, sub = jax.random.split(self._key)
            outs = fn(self.params, *self._adapter_args(),
                      *self._cache_args(),
                      jnp.asarray(toks), drafted, q_at, q_vals,
                      q_idx, jp, jtab,
                      *self._batch_adapter_operands(reqs, bucket),
                      *samp, sub)
            first = self._perf.done("verify", bucket) or first
            rec = None
            if self._sprof.tracing:
                # the draft rides in the verify's part: its outputs stay
                # on the device
                rec = self._sprof.enqueued("verify", bucket, B, outs[0],
                                           first)
            self._sprof.wait("serve.decode", rec, **note)
            emit_rows, acc, lp, tv, ti = self._unpack_outs(
                *self._adopt(outs, 5), "verify_logits", batch_size=B,
                rids=[r.rid for r in reqs])
            self._sprof.enter("host_sync")
            emitted = 0
            now = self.clock()
            for i, req in enumerate(reqs):
                accepted = int(acc[i])
                emit = [int(x) for x in emit_rows[i][:accepted + 1]]
                # the verify wrote every candidate position's K/V —
                # the draft loop did too, so the next draft never has
                # an ingest gap
                sw.note_drafted(req, int(pos[i]) + k + 1)
                emit = emit[:req.max_new_tokens - len(req.tokens)]
                accepted = min(accepted, len(emit))
                sw.on_verify(k, accepted)
                self._stats.on_verify(k, accepted,
                                      stochastic=req.temperature > 0.0)
                req.tokens.extend(emit)
                self._note_logprobs(req, lp[i][:len(emit)],
                                    tv[i][:len(emit)],
                                    ti[i][:len(emit)])
                req.cache_len += len(emit)
                emitted += len(emit)
                self._stats.on_tokens(req, len(emit), now=now)
                self._rtrace.event(req, "decode", batch=self._step_id,
                                   batch_size=B,
                                   tokens=len(req.tokens),
                                   emitted=len(emit), accepted=accepted)
                self._maybe_finish(req)
                if req.done:
                    sw.forget(req.rid)
                else:
                    self.blocks.truncate(req.rid, req.cache_len)
            return emitted
        douts = self._draft_fn(bucket)(
            sw.params, sw.cache_k, sw.cache_v, jnp.asarray(toks),
            jp, jtab, sub)
        first = self._perf.done("draft", bucket)
        drafted, sw.cache_k, sw.cache_v = douts
        rec = None
        if self._sprof.tracing:
            rec = self._sprof.enqueued("draft", bucket, B, drafted, first)
        self._sprof.wait("serve.decode", rec, **note)
        # mxtpu-lint: disable=host-sync (designed sync point: the
        # drafted ids feed the verify dispatch's host-built rows)
        drafted = np.asarray(drafted)
        self._sprof.enter("decode_dispatch")
        rows = np.zeros((bucket, k + 1), np.int32)
        rows[:, 0] = toks
        rows[:, 1:] = drafted
        fn = self._verify_fn(bucket)
        self._key, sub = jax.random.split(self._key)
        outs = fn(self.params, *self._adapter_args(),
                  *self._cache_args(),
                  jnp.asarray(rows), jp, jtab,
                  *self._batch_adapter_operands(reqs, bucket), sub)
        first = self._perf.done("verify", bucket)
        rec = None
        if self._sprof.tracing:
            rec = self._sprof.enqueued("verify", bucket, B, outs[0], first)
        self._sprof.wait(None, rec)
        if self._cfg.numeric_watch:
            out, ok = outs[0], outs[1]
            self._set_caches(outs[2:])
            # one batched read for tokens + watchdog flag
            # mxtpu-lint: disable=host-sync (designed sync point:
            # acceptance needs the target tokens on the host)
            out, ok = jax.device_get((out, ok))
            if not ok:
                flight_mod.record_anomaly(
                    "verify_logits", step=self._step_id,
                    batch_size=B, rids=[r.rid for r in reqs])
        else:
            out = outs[0]
            self._set_caches(outs[1:])
            # mxtpu-lint: disable=host-sync (designed sync point:
            # acceptance needs the target tokens on the host)
            out = np.asarray(out)
        self._sprof.enter("host_sync")
        emitted = 0
        for i, req in enumerate(reqs):
            accepted, emit = spec_mod.accept_greedy(drafted[i], out[i], k)
            # the verify wrote every candidate position's K/V — the
            # draft loop did too, so the next draft never has a gap
            sw.note_drafted(req, int(pos[i]) + k + 1)
            # a run that would overshoot the generation quota is capped
            # exactly where plain decode would have stopped
            emit = emit[:req.max_new_tokens - len(req.tokens)]
            # acceptance accounting counts only drafts that were
            # actually EMITTED — a quota-capped final iteration must
            # not inflate the rate with agreed-but-discarded drafts
            accepted = min(accepted, len(emit))
            sw.on_verify(k, accepted)
            self._stats.on_verify(k, accepted)
            req.tokens.extend(emit)
            req.cache_len += len(emit)
            emitted += len(emit)
            self._stats.on_tokens(req, len(emit))
            self._rtrace.event(req, "decode", batch=self._step_id,
                               batch_size=B, tokens=len(req.tokens),
                               emitted=len(emit), accepted=accepted)
            self._maybe_finish(req)
            if req.done:
                sw.forget(req.rid)
            else:
                # roll back the speculative tail: blocks reserved past
                # the accepted sequence return to the free list (never
                # a shared prefix block — truncate stops at refcount>1)
                self.blocks.truncate(req.rid, req.cache_len)
        return emitted

    def _maybe_finish(self, req):
        if len(req.tokens) >= req.max_new_tokens:
            self.scheduler.finish(req, status=FINISHED)
            self._stats.on_complete(req)

    # -- AOT warmup / manifests (mxnet_tpu/aot/) -----------------------------
    def manifest(self):
        """The (kind, bucket) programs this engine has executed so far
        — the traffic-replay warmup manifest (list of entry dicts)."""
        return self._manifest.entries()

    def save_manifest(self, path):
        """Write the manifest as JSONL for a later ``warmup(path)``."""
        self._read_flight()
        with open(path, "w") as f:
            for e in self._manifest.entries():
                f.write(json.dumps(e) + "\n")
        return path

    def warmup(self, manifest=None):
        """Compile (or AOT-load) every program ``manifest`` lists,
        before traffic arrives.

        ``manifest`` is a JSONL path, an iterable of entry dicts
        (another engine's :meth:`manifest`), or None — which replays
        ``MXTPU_WARMUP_MANIFEST`` when set, else warms the full bucket
        grid (every decode batch bucket and power-of-two prompt bucket
        this config can serve).  Entries recorded by an incompatibly-
        configured engine, or outside this engine's bucket range, are
        skipped.  Returns the number of programs made ready.
        """
        if not self._alive:
            raise RuntimeError("engine is shut down")
        entries = aot_warmup.load_manifest(manifest, self._spec_digest)
        if not entries and manifest is None:
            entries = self._warmup_grid()
        elif self._host_pool is not None:
            # the host tier shares the tier-off engines' programs AND
            # fingerprints (it changes no existing program), so a
            # manifest recorded by a tier-off predecessor replays
            # cleanly — but it lists no restore programs.  Force the
            # (small) restore ladder in, or the first host-tier radix
            # hit after an upgrade would trace mid-step
            entries = list(entries) + [
                {"kind": "restore", "bucket": b}
                for b in self._bucket_ladder(self.table_width)]
        # kind -> largest bucket this engine runs it at (absent: not its)
        caps = {"decode": self.max_batch, "prefill": self.max_model_len,
                "chunk": self._chunk_cap()}
        if self._spec is not None:
            caps.update(verify=self.max_batch, draft=self.max_batch,
                        draft_chunk=self.max_model_len)
        if self._host_pool is not None:
            caps["restore"] = self.table_width
        ready = 0
        self._warming = True   # warmup must not re-record the manifest
        try:
            with telemetry.span("serve.warmup", programs=len(entries)):
                for e in entries:
                    cap, bucket = caps.get(e["kind"]), int(e["bucket"])
                    if cap is not None and 1 <= bucket <= cap:
                        bucket = _next_bucket(bucket, cap)
                        self._program(e["kind"], bucket)
                        ready += 1
                        # the token pool's small programs come ready with
                        # the entries they serve (no kind of their own)
                        if self._tok_pool is None:
                            continue
                        if e["kind"] == "decode":
                            self._token_program(TOK_TAKE, bucket)
                            self._token_program(TOK_PUT, bucket)
                        elif (e["kind"] in ("prefill", "chunk")
                              and not self._diffusion):
                            # (a diffusion prefill puts no token there)
                            self._token_program(TOK_PUT1)
        finally:
            self._warming = False
        return ready

    def _warmup_grid(self):
        """Every program this config can ever run: the offline pre-bake
        default when no traffic manifest exists yet.  Reachable buckets
        are the powers of two below each cap PLUS the cap itself —
        ``_next_bucket`` clamps, so a non-power-of-two cap is a real
        bucket live traffic hits."""
        buckets = self._bucket_ladder
        grid = ([{"kind": "decode", "bucket": b}
                 for b in buckets(self.max_batch)]
                + [{"kind": "prefill", "bucket": p}
                   for p in buckets(self.max_model_len)]
                # suffix/chunk prefills (prefix-cache hits + chunked
                # long prompts) run their own program family — a warm
                # restart must be zero-fresh-trace for those too
                + [{"kind": "chunk", "bucket": c}
                   for c in buckets(self._chunk_cap())])
        if self._spec is not None:
            # speculative decoding adds three families: the target
            # verify pass and the draft's propose/ingest programs — a
            # spec-enabled warm restart must be zero-fresh-trace too
            grid += ([{"kind": "verify", "bucket": b}
                      for b in buckets(self.max_batch)]
                     + [{"kind": "draft", "bucket": b}
                        for b in buckets(self.max_batch)]
                     + [{"kind": "draft_chunk", "bucket": c}
                        for c in buckets(self.max_model_len)])
        if self._host_pool is not None:
            # the host tier's restore family exists ONLY when the tier
            # is on (the only-when-on rule: a tier-off engine's grid,
            # manifests and fingerprints are untouched)
            grid += [{"kind": "restore", "bucket": b}
                     for b in buckets(self.table_width)]
        return grid

    # -- compiled programs ---------------------------------------------------
    def _decode_fn(self, B):
        return self._program("decode", B)

    def _prefill_fn(self, P):
        return self._program("prefill", P)

    def _chunk_fn(self, C):
        return self._program("chunk", C)

    def _verify_fn(self, B):
        return self._program("verify", B)

    def _draft_fn(self, B):
        return self._program("draft", B)

    @staticmethod
    def _bucket_ladder(cap):
        """Power-of-two buckets up to (and always including) ``cap`` —
        THE bucket enumeration: the warmup grid and every bucket view
        (statusz verify_buckets) must agree with what live traffic's
        ``_next_bucket`` clamp can hit."""
        out, b = [], 1
        while b < cap:
            out.append(b)
            b *= 2
        return out + [cap]

    def verify_buckets(self):
        """The verify program family's bucket grid (empty when
        speculative decoding is off) — the /statusz ``spec`` section's
        'which programs exist' view."""
        if self._spec is None:
            return []
        return self._bucket_ladder(self.max_batch)

    def _chunk_cap(self):
        """Largest chunk-program bucket live traffic can hit.  With
        chunking on, a non-chunked suffix is <= prefill_chunk by the
        scheduler's lane test and a chunk is <= the budget; with
        chunking off only prefix-hit suffixes use the chunk program,
        and those can reach the full model length."""
        chunk = self.scheduler.prefill_chunk
        if chunk > 0:
            return _next_bucket(chunk, self.max_model_len)
        return self.max_model_len

    def _program(self, kind, bucket):
        key = (self._spec_key(), kind, bucket)
        fn = _STEP_CACHE.get(key)
        if fn is None:
            fn = self._resolve_program(kind, bucket)
            _STEP_CACHE[key] = fn
        if not self._warming:
            self._manifest.record(kind, bucket)
        if self._perf.enabled and self._perf.cost(kind, bucket) is None:
            # cost-table capture sits HERE — the one chokepoint all
            # three resolve paths share (fresh trace, warm AOT load,
            # and a process-local _STEP_CACHE hit from a twin engine),
            # so a warm-started engine never reports an empty perf
            # section.  Idempotent per (kind, bucket): after the first
            # capture this is one dict probe per dispatch.
            af, ab = self._analytic_cost(kind, bucket)
            self._perf.note_cost(kind, bucket, fn,
                                 fallback_flops=af, fallback_bytes=ab)
        return fn

    def _analytic_cost(self, kind, bucket):
        """Analytic (flops, bytes) estimate for one (kind, bucket)
        dispatch, from the GQA-aware closed forms in ``flops.py`` over
        the PADDED program shapes (bucket rows, table-capacity
        context).  The cost-table fallback when a backend exposes no
        ``cost_analysis()``, and the cross-check pinned against it in
        tests/test_perf_contract.py."""
        from .. import flops as flops_mod

        if self._desc is not None:
            # 2 operations per matrix parameter per row, the tied head
            # once per sampled row; attention's score terms left out
            rows = 1 if kind != "decode" else bucket
            if kind == "decode" and self._diffusion:
                # a denoise pass runs, and samples, every block position
                bucket = rows = bucket * self._block
            return (hybrid_mod.matmul_flops(self._desc, bucket, rows),
                    None)
        if kind in ("draft", "draft_chunk") and self._spec is not None:
            cfg, params = self._spec.cfg, self._spec.params
        else:
            cfg, params = self._cfg, self.params
        try:
            tok_w = params[f"{cfg.name}_tok_embed_weight"]
            ffw = params.get(f"{cfg.name}_l0_ff_up_weight")
            kw = dict(n_layers=cfg.n_layers,
                      d_model=int(tok_w.shape[1]),
                      num_heads=cfg.num_heads, head_dim=cfg.head_dim,
                      kv_heads=cfg.kv_heads, vocab=int(tok_w.shape[0]),
                      d_ff=int(ffw.shape[0]) if ffw is not None else None,
                      swiglu=cfg.swiglu)
        except Exception:
            return None, None          # params already freed (shutdown)
        ctx = self.table_width * self.block_size
        per_tok = flops_mod.gpt_token_flops(context=ctx, **kw)
        if kind == "prefill":
            return flops_mod.gpt_prefill_flops(seq_len=bucket, **kw), None
        if kind in ("chunk", "draft_chunk"):
            return bucket * per_tok, None
        if kind == "verify":
            return bucket * (self.spec_k + 1) * per_tok, None
        if kind == "draft":
            return bucket * self.spec_k * per_tok, None
        if kind == "restore":
            # pure copy program: no matmuls — bytes are the K+V block
            # payload in and out (the MBU numerator)
            L, bs = self._cfg.n_layers, self.block_size
            Hkv, Dh = self._cfg.kv_heads, self._cfg.head_dim
            payload = (2 * L * bucket * bs * Hkv * Dh
                       * self._cache_k.dtype.itemsize)
            return None, 2 * payload
        return bucket * per_tok, None      # decode

    def _program_specs(self, kind, bucket):
        """ShapeDtypeStructs matching exactly what _run_prefill /
        _run_decode pass — the export/AOT-compile signature.  Under
        tensor parallelism each spec carries its NamedSharding: that is
        what lets ``.lower(specs).compile()`` AOT-compile the sharded
        program (and export/reload it) without example arrays."""
        i32 = jnp.dtype(jnp.int32)
        sh = self._shardings

        def sds(shape, dtype, sharding=None):
            if sh is None:
                return jax.ShapeDtypeStruct(shape, dtype)
            return jax.ShapeDtypeStruct(shape, dtype,
                                        sharding=sharding or sh.rep)

        kspec = sds(self._key.shape, self._key.dtype)
        f32 = jnp.dtype(jnp.float32)

        def samp(shape):
            # the sampling-mode programs' per-request operand triple
            # (temperature, top_p, top_k) — absent on greedy engines,
            # whose program signatures are the historical ones
            if not self._cfg.sampling:
                return ()
            return (sds(shape, f32), sds(shape, f32), sds(shape, i32))

        def adp():
            # the LoRA device-stack pytree right after the params —
            # absent on adapters-off engines (historical signatures)
            if not self._cfg.adapters:
                return ()
            ash = self.adapter_store.sharding or {}
            return ({k: sds(v.shape, v.dtype,
                            ash.get(k) if sh is not None else None)
                     for k, v in self.adapter_store.device.items()},)

        def aslot(shape):
            # the per-request adapter-slot index operand (scalar for
            # prefill/chunk, (B,) for decode/verify); a state pool's
            # slot operand sits in the same place, the same shape, and a
            # window group's operands behind it: its tables for decode,
            # its table and write blocks for a chunk, its write blocks
            # for a whole prompt
            out = ()
            if self._cfg.adapters or self._state_ssm is not None:
                out += (sds(shape, i32),)
            if self.blocks.window is not None:
                table = sds(shape + (self.table_width,), i32)
                if kind == "decode":
                    return out + (table,)
                out += ((table,) if kind == "chunk" else ()) \
                    + (sds((bucket,), i32),)
            return out

        if kind in ("draft", "draft_chunk"):
            # draft-side programs: the draft checkpoint's params and
            # its own (replicated-under-tp) cache pair, the target's
            # table geometry
            sw = self._spec
            dpspec = {k: sds(v.shape, v.dtype)
                      for k, v in sw.params.items()}
            dcspec = sds(sw.cache_k.shape, sw.cache_k.dtype)
            if kind == "draft":
                return (dpspec, dcspec, dcspec, sds((bucket,), i32),
                        sds((bucket,), i32),
                        sds((bucket, self.table_width), i32)) \
                    + samp((bucket,)) + (kspec,)
            # draft_chunk: toks, start, n_valid, table, blk, off, rng
            return (dpspec, dcspec, dcspec, sds((bucket,), i32),
                    sds((), i32), sds((), i32),
                    sds((self.table_width,), i32),
                    sds((bucket,), i32), sds((bucket,), i32), kspec)
        pspec = {k: sds(v.shape, v.dtype,
                        sh.params[k] if sh is not None else None)
                 for k, v in self.params.items()}
        cspec = sds(self._cache_k.shape, self._cache_k.dtype,
                    sh.cache if sh is not None else None)
        # int8-KV engines thread the two scale arrays right after the
        # caches in every target-model program (same order as
        # _cache_args)
        caches = (cspec, cspec)
        # what a description brings follows the K/V (same order as
        # _cache_args): the state pool, the window group's stack, the probe
        caches += tuple(sds(getattr(self, a).shape, getattr(self, a).dtype)
                        for a in self._extra_caches)
        if self._kv_quant:
            sspec = sds(self._scale_k.shape, self._scale_k.dtype,
                        sh.scale if sh is not None else None)
            caches = (cspec, cspec, sspec, sspec)
        if kind == "restore":
            # host-tier restore: caches first (no params, no rng),
            # then the block ids and the replicated host copies —
            # blks, hk, hv[, hks, hvs] (same order as _restore_pending)
            L, bs = self._cfg.n_layers, self.block_size
            Hkv, Dh = self._cfg.kv_heads, self._cfg.head_dim
            hspec = sds((L, bucket, bs, Hkv, Dh), self._cache_k.dtype)
            specs = caches + (sds((bucket,), i32), hspec, hspec)
            if self._kv_quant:
                s = sds((L, bucket, bs, Hkv), jnp.dtype(jnp.float32))
                specs += (s, s)
            return specs
        if kind == "decode" and self._diffusion:
            # blocks (R, B), their first positions, tables, the counts
            return (pspec,) + caches + (
                sds((bucket, self._block), i32), sds((bucket,), i32),
                sds((bucket, self.table_width), i32),
                sds((bucket,), i32), kspec)
        if kind == "decode":
            return (pspec,) + adp() + caches + (sds((bucket,), i32),
                    sds((bucket,), i32),
                    sds((bucket, self.table_width), i32)) \
                + aslot((bucket,)) + samp((bucket,)) + (kspec,)
        if kind == "verify":
            if self._cfg.sampling:
                # toks (B,), drafted (B, k), then the draft's q in
                # candidate space — q_at (B, k), q_vals/q_idx
                # (B, k, cap) — device-to-device from the draft
                # dispatch; pos0, tables, the operand triple, rng
                cap = min(self.sample_cap, self.spec["vocab"])
                return (pspec,) + adp() + caches + (
                        sds((bucket,), i32),
                        sds((bucket, self.spec_k), i32),
                        sds((bucket, self.spec_k), f32),
                        sds((bucket, self.spec_k, cap), f32),
                        sds((bucket, self.spec_k, cap), i32),
                        sds((bucket,), i32),
                        sds((bucket, self.table_width), i32)) \
                    + aslot((bucket,)) + samp((bucket,)) + (kspec,)
            # rows (B, k+1), pos0 (B,), tables (B, W), rng
            return (pspec,) + adp() + caches + (
                    sds((bucket, self.spec_k + 1), i32),
                    sds((bucket,), i32),
                    sds((bucket, self.table_width), i32)) \
                + aslot((bucket,)) + (kspec,)
        if kind == "chunk":
            # toks, start, n_valid, table, blk, off, rng
            return (pspec,) + adp() + caches + (sds((bucket,), i32),
                    sds((), i32), sds((), i32),
                    sds((self.table_width,), i32),
                    sds((bucket,), i32), sds((bucket,), i32)) \
                + aslot(()) + samp((1,)) + (kspec,)
        return (pspec,) + adp() + caches + (sds((bucket,), i32),
                sds((), i32),
                sds((bucket,), i32), sds((bucket,), i32)) \
            + aslot(()) + samp((1,)) + (kspec,)

    def _program_builder(self, kind, bucket):
        """The freshly-traced jitted program for (kind, bucket) — the
        switch over program families, shared by ``_resolve_program``
        and ``tools/hlo_audit.py``'s serve lowering (which audits the
        exact builders traffic runs, not a reconstruction).  The
        builders close over immutable ``_ModelCfg``s only — never an
        Engine (the _STEP_CACHE retention rule)."""
        if self._cfg.hybrid is not None:
            # the hybrid family: one layer function under three builders
            if kind == "decode":
                return hybrid_mod.build_decode(self._cfg, self._donate)
            if kind == "chunk":
                return hybrid_mod.build_chunk(self._cfg, bucket,
                                              self._donate)
            return hybrid_mod.build_prefill(self._cfg, bucket,
                                            self._donate)
        if kind == "decode":
            return _build_decode(self._cfg, self._donate,
                                 self._shardings)
        if kind == "chunk":
            return _build_chunk(self._cfg, bucket, self._donate,
                                self._shardings)
        if kind == "verify":
            return spec_mod._build_verify(self._cfg, self.spec_k,
                                          self._donate,
                                          self._shardings)
        if kind == "draft":
            # sampling engines draft by SAMPLING from the warped
            # distribution (sample_cfg carries the target cfg's
            # cap/operand layout); greedy engines keep the
            # historical argmax draft program byte-for-byte
            return spec_mod._build_draft(
                self._spec.cfg, self.spec_k, self._donate,
                self._draft_shardings,
                sample_cfg=(self._cfg if self._cfg.sampling
                            else None))
        if kind == "draft_chunk":
            return _build_chunk(self._spec.cfg, bucket, self._donate,
                                self._draft_shardings)
        if kind == "restore":
            return _build_restore(self._cfg, self._donate,
                                  self._shardings)
        return _build_prefill(self._cfg, bucket, self._donate,
                              self._shardings)

    def _resolve_program(self, kind, bucket):
        """One bucket program: AOT-load it from the export store, or
        trace it fresh (and write it through for the next restart).
        ``mxtpu_aot_programs_total{kind,source}`` counts which happened
        — ``source="trace"`` is exactly a cold-start compile the warm
        path is supposed to avoid — and the ``serve.resolve`` span, with
        its children ``.build`` (trace + export, or the artifact's load;
        lowering either way) and ``.compile`` (XLA; a compile-cache read
        on a warm start), says what each program cost at start-up.

        Every path eagerly compiles (``.lower(specs).compile()``): on
        the hot path the compile was due this very step anyway, and
        eagerness is what makes ``warmup()`` mean "ready" rather than
        "will compile at the first unlucky request".  A compile failure
        (a kernel Mosaic refuses, a compile-time OOM) raises here — a
        program that cannot compile is not ready."""
        specs = self._program_specs(kind, bucket)
        with telemetry.span("serve.resolve", kind=kind,
                            bucket=int(bucket)) as resolve:
            with telemetry.span("serve.resolve.build") as build:
                exported = None
                if self._aot is not None:
                    fp = dict(self._aot_base_fp(), kind=kind,
                              bucket=int(bucket))
                    label = f"serve-{kind}{bucket}"
                    exported = self._aot.load(fp, label=label)
                source = "trace" if exported is None else "artifact"
                resolve.set(source=source)
                build.set(source=source)
                telemetry.counter(
                    "mxtpu_aot_programs_total", "bucket-program resolutions",
                    ("kind", "source")).labels(kind=kind,
                                               source=source).inc()
                if exported is None:
                    jitted = self._program_builder(kind, bucket)
                    if self._aot is not None:
                        exported = jax.export.export(jitted)(*specs)
                        self._aot.save(fp, exported, label=label)
                if exported is not None:
                    # both the cold and the warm process execute the
                    # round-tripped module, so the XLA compile below has
                    # the same persistent-cache key in both
                    jitted = jax.jit(
                        exported.call,
                        donate_argnums=self._donated_argnums(kind))
                lowered = jitted.lower(*specs)
            with telemetry.span("serve.resolve.compile"):
                return lowered.compile()

    def _donated_argnums(self, kind):
        """Positions of the cache operands a program donates."""
        if not self._donate:
            return ()
        n_caches = (4 if (self._cfg.kv_quant or self._cfg.hybrid)
                    and kind not in ("draft", "draft_chunk") else 2)
        # the restore program has no params operand: its donated cache
        # arguments START the signature instead of following the pytree.
        # Adapter-mode target programs carry the LoRA stack pytree
        # between the params and the caches, shifting the donated
        # argnums by one more (draft programs stay base-model)
        if kind == "restore":
            first = 0
        elif self._cfg.adapters and kind not in ("draft", "draft_chunk"):
            first = 2
        else:
            first = 1
        return tuple(range(first, first + n_caches))
