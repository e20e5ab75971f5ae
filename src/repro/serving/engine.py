"""Serving engine: deploys a model on the Provuse platform as a FaaS
function *chain* and serves batched prefill/decode through it.

Chain layout (blocks families — dense/moe/vlm/ssm):

    <arch>/embed  ->  <arch>/g0  ->  ...  ->  <arch>/g{G-1}  ->  <arch>/head

Each stage is an independently deployed function holding its own layer-slice
weights; every stage synchronously calls the next and returns the final
result back up the chain — while the head computes, every upstream instance
is blocked (the paper's double-billing chain). enc-dec archs deploy the
canonical two-function app (encoder -> decoder); hybrid deploys
embed -> core -> head.

The platform observes the synchronous edges during live traffic and fuses
the chain step by step into a single XLA program per request type — no code
here ever asks for fusion; it *happens to* the deployment (transparent,
platform-side). Per-token latency before/after is the paper's Fig. 5.

Stage functions are shape-polymorphic: a (B, T>1) input takes the prefill
path (and scatter-fills the preallocated max_len cache); (B, 1) takes the
decode path. One deployed function serves both request types, mirroring a
FaaS function with two routes.

Host phases of a paged call (``engine.decode_step`` / ``engine.prefill_chunk``
around ``engine.caches`` and ``engine.store_back``) are the platform
tracer's ``phase`` annotations: inert unless a profiler trace runs.

Paged serving: with ``enable_paging`` the decode route can also serve from
a shared :class:`~repro.serving.kvpool.KVArena` — ``caches`` then carries a
block table plus each stage's page-pool slice instead of per-client dense
pytrees, and the SAME deployed (possibly fused) chain reads/writes arena
pages. Fused and unfused chains serve from one arena, so fusion benchmarks
measure the paper's effect at realistic occupancy (see
``serving/continuous.py`` for the decode loop that keeps it busy).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.dispatch import TRACER
from repro.configs.base import ModelConfig
from repro.core.function import COMPILER_OPTIONS, FunctionSpec
from repro.core.platform import ProvusePlatform
from repro.models import encdec as ed
from repro.models import hybrid as hy
from repro.models import transformer as tfm
from repro.models.layers import apply_norm, embed_tokens, unembed
from repro.models.model import Model
from repro.models.params import init_params
from repro.serving.kvpool import KVArena

#: Greedy sampling as ONE compiled device step: the previous inline
#: ``jnp.argmax(jnp.asarray(logits))`` dispatched eagerly and forced a host
#: sync per token, so the timed per-token loop measured transfer stalls,
#: not device time.
_greedy_token = jax.jit(
    lambda logits: jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
)


class PagedPrefillJob:
    """Host-side cursor for one chunked paged prefill: ``pos`` tracks how
    many prompt tokens already have resident KV (cached prefix pages count
    immediately), ``t_in`` is the full prompt length."""

    __slots__ = ("seq_id", "tokens", "pos")

    def __init__(self, seq_id, tokens: np.ndarray, pos: int):
        self.seq_id = seq_id
        self.tokens = tokens  # (t_in,) int32
        self.pos = pos

    @property
    def t_in(self) -> int:
        return len(self.tokens)

    @property
    def remaining(self) -> int:
        return self.t_in - self.pos


def _stage(fn, params, x, *args):
    """``fn(params, x, *args)``: one chain stage's layers. Traced into a
    fused unit, they are inlined, and the platform compiles the unit with
    its options (``core.function.COMPILER_OPTIONS``); run glued, they are
    compiled as one program with the same options, so the layers compute
    the same bits either way. Glued, ``fn`` is a new closure on each call
    and compiles on each call, as the eager glue's layer scan always did."""
    if isinstance(x, jax.core.Tracer):
        return fn(params, x, *args)
    return jax.jit(fn, compiler_options=COMPILER_OPTIONS)(params, x, *args)


def _slice_tree(tree, lo: int, hi: int):
    return jax.tree.map(lambda x: x[lo:hi], tree)


def _pick_groups(n_layers: int, requested: int) -> int:
    g = min(requested, n_layers)
    while g > 1 and n_layers % g:
        g -= 1
    return max(1, g)


class ServingEngine:
    """Deploys ``model`` as a function chain on ``platform``.

    Weights are drawn from ``seed`` and held once, by the deployed
    functions: each stage's spec holds the only copy of its layers and the
    engine keeps no full tree. The blocks chain draws every stage's weights
    on its own, so a full-width model never exists as one stacked tree."""

    def __init__(self, model: Model, platform: ProvusePlatform, *, max_len: int = 256,
                 seed: int = 0, trust_domain: str | None = None,
                 kv_pages: int = 0, kv_page_size: int = 16):
        self.model = model
        self.cfg = model.cfg
        self.platform = platform
        self.max_len = max_len
        self.prefix = self.cfg.name
        self.trust = trust_domain or self.cfg.name
        self.entry = f"{self.prefix}/embed"
        self.arena: KVArena | None = None
        #: the last paged step's expert row counts, one (layers, E) int32
        #: device array per stage, or None for a model without experts
        self.expert_rows: tuple | None = None
        key = jax.random.PRNGKey(seed)
        fam = self.cfg.family
        if fam in ("dense", "moe", "vlm", "ssm"):
            self._deploy_blocks_chain(key)
        elif fam == "audio":
            self._deploy_encdec_chain(model.init(key))
        elif fam == "hybrid":
            self._deploy_monolithic_chain(model.init(key))
        else:
            raise ValueError(fam)
        if kv_pages:
            self.enable_paging(kv_pages, kv_page_size)

    # ------------------------------------------------------------ chains

    def _deploy_blocks_chain(self, key) -> None:
        cfg = self.cfg
        L = cfg.num_layers
        g = _pick_groups(L, cfg.num_function_groups)
        per = L // g
        kind = "moe" if cfg.family == "moe" else ("ssm" if cfg.family == "ssm" else "dense")
        names = [f"{self.prefix}/g{i}" for i in range(g)]
        head_name = f"{self.prefix}/head"
        keys = jax.random.split(key, g + 1)
        defs = self.model.param_defs
        shared = init_params({"embed": defs["embed"], "ln_f": defs["ln_f"]}, keys[0])
        group_params = [
            init_params(tfm.stack_block_defs(cfg, kind, per), keys[1 + i]) for i in range(g)
        ]

        def embed_fn(ctx, params, inputs, cur_len, caches):
            if "tokens" in inputs:
                x = embed_tokens(params, inputs["tokens"])
            else:
                x = inputs["embeds"]
            return ctx.call(names[0], x, cur_len, caches)

        self.platform.deploy(
            FunctionSpec(self.entry, embed_fn, {"table": shared["embed"]["table"]}, self.trust)
        )

        def make_group_fn(i: int):
            key = f"g{i}"
            nxt = names[i + 1] if i + 1 < g else head_name
            first = i * per  # the model index of the stage's first layer

            def group_fn(ctx, params, x, cur_len, caches):
                if "block_table" in caches:  # paged: caches hold the arena
                    if "chunk_valid" in caches:  # chunked-prefill rows
                        h, new_arena, metrics = _stage(
                            lambda p, x, a, t, s, v: tfm.apply_stack_prefill_chunk_paged(
                                p, x, a, t, cfg, kind, None, s, v, first),
                            params, x, caches[key], caches["block_table"], cur_len,
                            caches["chunk_valid"])
                    else:  # single-token decode ("__frozen__" = no KV write)
                        write_kv = "__frozen__" not in caches
                        h, new_arena, metrics = _stage(
                            lambda p, x, a, t, c: tfm.apply_stack_decode_paged(
                                p, x, a, t, cfg, kind, None, c, write_kv, first),
                            params, x, caches[key], caches["block_table"], cur_len)
                    caches = dict(caches)
                    caches[key] = new_arena
                    if tfm.EXPERT_ROWS in metrics:  # (per, E) rows per expert
                        caches[f"{key}.{tfm.EXPERT_ROWS}"] = metrics[tfm.EXPERT_ROWS]
                    return ctx.call(nxt, h, cur_len, caches)
                old = caches[key]
                if x.shape[1] == 1:  # decode
                    h, new_cache, _ = _stage(
                        lambda p, x, c, n: tfm.apply_stack_decode(p, x, c, cfg, kind, None, n,
                                                                  first_layer=first),
                        params, x, old, cur_len)
                else:  # prefill: build the cache and scatter into max_len slots
                    h, built, _ = _stage(
                        lambda p, x: tfm.apply_stack_full(
                            p, x, cfg, kind, None, jnp.arange(x.shape[1])[None, :],
                            collect_cache=True, first_layer=first),
                        params, x)
                    if kind == "ssm":
                        new_cache = built
                    else:
                        new_cache = jax.tree.map(
                            lambda full, part: jax.lax.dynamic_update_slice(
                                full, part.astype(full.dtype), (0, 0, 0, 0, 0)
                            ),
                            old,
                            built,
                        )
                caches = dict(caches)
                caches[key] = new_cache
                return ctx.call(nxt, h, cur_len, caches)

            return group_fn

        for i, name in enumerate(names):
            self.platform.deploy(FunctionSpec(name, make_group_fn(i), group_params[i], self.trust))

        def head_fn(ctx, params, x, cur_len, caches):
            if isinstance(caches, dict) and "chunk_valid" in caches:
                # chunked prefill pads the chunk to a power of two: the last
                # REAL row's hidden state is at chunk_valid - 1, not -1
                h = jax.lax.dynamic_slice_in_dim(x, caches["chunk_valid"][0] - 1, 1, axis=1)
            else:
                h = x[:, -1:]
            h = apply_norm(params["ln_f"], h, cfg)
            logits = unembed(params["embed"], h)[:, 0]
            return logits, caches

        self.platform.deploy(FunctionSpec(head_name, head_fn, shared, self.trust))
        self.group_names = names
        self.kind = kind

    def _deploy_encdec_chain(self, params) -> None:
        cfg = self.cfg
        dec_name = f"{self.prefix}/decoder"

        def enc_fn(ctx, params, inputs, cur_len, caches):
            enc, _ = ed.encode(params, inputs["src_embeds"], cfg, None)
            return ctx.call(dec_name, enc, inputs["tokens"], cur_len, caches)

        def dec_fn(ctx, params, *args):
            if len(args) == 4:  # prefill: (enc, tokens, cur_len, caches)
                enc, tokens, cur_len, caches = args
                cross = ed.cross_kv_from_enc(params["encdec"], enc)
                x = embed_tokens(params["embed"], tokens)
                src_len = jnp.full((x.shape[0],), enc.shape[1], jnp.int32)
                h, new_self, _ = ed.decoder_step(
                    params["encdec"], x, caches["self"], cross, cfg, None, cur_len, src_len
                )
                caches = {"self": new_self, "cross": cross}
            else:  # decode: (tokens, cur_len, caches)
                tokens, cur_len, caches = args
                x = embed_tokens(params["embed"], tokens)
                src = caches["cross"]["k"].shape[2]
                src_len = jnp.full((x.shape[0],), src, jnp.int32)
                h, new_self, _ = ed.decoder_step(
                    params["encdec"], x, caches["self"], caches["cross"], cfg, None, cur_len, src_len
                )
                caches = {"self": new_self, "cross": caches["cross"]}
            h = apply_norm(params["ln_f"], h, cfg)
            logits = unembed(params["embed"], h)[:, 0]
            return logits, caches

        enc_params = {"encoder": params["encdec"]["encoder"]}
        dec_params = {
            "encdec": {"decoder": params["encdec"]["decoder"]},
            "embed": params["embed"],
            "ln_f": params["ln_f"],
        }
        # encode() expects params["encoder"]; decoder fns expect the nested form
        self.platform.deploy(FunctionSpec(self.entry, enc_fn, enc_params, self.trust))
        self.platform.deploy(FunctionSpec(dec_name, dec_fn, dec_params, self.trust))
        self.dec_name = dec_name

    def _deploy_monolithic_chain(self, params) -> None:
        cfg = self.cfg
        core_name = f"{self.prefix}/core"
        head_name = f"{self.prefix}/head"

        def embed_fn(ctx, params, inputs, cur_len, caches):
            x = embed_tokens(params, inputs["tokens"])
            return ctx.call(core_name, x, cur_len, caches)

        def core_fn(ctx, params, x, cur_len, caches):
            if x.shape[1] == 1:
                h, new_caches, _ = hy.apply_hybrid_decode(params, x, caches, cfg, None, cur_len)
            else:
                positions = jnp.arange(x.shape[1])[None, :]
                h, built, _ = hy.apply_hybrid_full(params, x, cfg, None, positions, collect_cache=True)
                new_caches = dict(caches)
                new_caches["groups"] = built["groups"]
                if "tail" in built:
                    new_caches["tail"] = built["tail"]
                new_caches["attn"] = jax.tree.map(
                    lambda full, part: jax.lax.dynamic_update_slice(
                        full, part.astype(full.dtype), (0, 0, 0, 0, 0)
                    ),
                    caches["attn"],
                    built["attn"],
                )
            return ctx.call(head_name, h, cur_len, new_caches)

        def head_fn(ctx, params, x, cur_len, caches):
            h = apply_norm(params["ln_f"], x[:, -1:], cfg)
            logits = unembed(params["embed"], h)[:, 0]
            return logits, caches

        self.platform.deploy(FunctionSpec(self.entry, embed_fn, {"table": params["embed"]["table"]}, self.trust))
        self.platform.deploy(FunctionSpec(core_name, core_fn, params["hybrid"], self.trust))
        self.platform.deploy(
            FunctionSpec(head_name, head_fn, {"ln_f": params["ln_f"], "embed": params["embed"]}, self.trust)
        )

    # ------------------------------------------------------- provisioning

    def chain_names(self) -> list[str]:
        """Every function name this engine deployed, in chain order."""
        fam = self.cfg.family
        if fam == "audio":
            return [self.entry, self.dec_name]
        if fam == "hybrid":
            return [self.entry, f"{self.prefix}/core", f"{self.prefix}/head"]
        return [self.entry, *self.group_names, f"{self.prefix}/head"]

    def scale_to_zero(self) -> tuple[str, ...]:
        """Park the whole serving chain as snapshots (platform must have
        snapshots enabled). Idle models stop paying for resident params; the
        next prefill/decode resurrects the chain from its snapshots. Returns
        the parked function names."""
        parked: list[str] = []
        for name in self.chain_names():
            if name in parked:
                continue  # co-parked as a member of an earlier fused group
            if self.platform.registry.get(name) is None:
                continue  # already parked (or never routed)
            parked.extend(self.platform.scale_to_zero(name))
        return tuple(parked)

    # ------------------------------------------------------------ caches

    def empty_caches(self, batch: int):
        from repro.configs.base import ShapeConfig

        shape = ShapeConfig("serve", self.max_len, batch, "decode")
        cache = init_params(self.model.cache_defs(shape), jax.random.PRNGKey(0))
        if self.cfg.family in ("dense", "moe", "vlm", "ssm"):
            # re-key the model-level (L, ...) cache by chain stage
            g = len(self.group_names)
            per = self.cfg.num_layers // g
            return {
                f"g{i}": _slice_tree(cache, i * per, (i + 1) * per) for i in range(g)
            }
        return cache

    # ------------------------------------------------------------ paging

    @property
    def paging_supported(self) -> bool:
        """Paged KV applies to length-indexed attention caches; SSM state is
        recurrent and enc-dec/hybrid keep their dedicated layouts."""
        return self.cfg.family in ("dense", "moe", "vlm")

    def enable_paging(self, num_pages: int, page_size: int = 16) -> KVArena:
        """Preallocate the shared KV arena: one (layers, pages, KV, page, hd)
        pool per chain stage, one allocator/block table across stages."""
        if not self.paging_supported:
            raise ValueError(f"paged KV unsupported for family {self.cfg.family!r}")
        if self.max_len % page_size:
            raise ValueError(f"max_len={self.max_len} must be a multiple of page_size={page_size}")
        g = len(self.group_names)
        per = self.cfg.num_layers // g
        self.arena = KVArena(
            {f"g{i}": per for i in range(g)},
            num_pages=num_pages,
            page_size=page_size,
            kv_heads=self.cfg.num_kv_heads,
            head_dim=self.cfg.head_dim,
            dtype=jnp.dtype(self.cfg.kv_cache_dtype),
        )
        self.block_width = self.arena.max_pages_per_seq(self.max_len)
        return self.arena

    def prefill_paged(self, seq_id, inputs: dict):
        """Admit one request into the arena: dense chain prefill (the
        prefill route is unchanged), then copy-on-prefill scatters the built
        cache into freshly allocated pages and the dense pytree is dropped.

        Token prompts go through the arena's shared-prefix cache: leading
        pages whose content hashes hit are held by reference and skipped by
        the scatter; a whole-prompt hit skips the dense prefill entirely —
        one frozen decode step at the last prompt position recovers the
        first-token logits from the cached pages (bit-exact: the masked
        padded positions contribute exact zeros, same as the dense path).
        Returns (last logits (1, V), prompt length)."""
        assert self.arena is not None, "enable_paging first"
        t_in = inputs["tokens"].shape[1] if "tokens" in inputs else inputs["embeds"].shape[1]
        if "tokens" in inputs:
            _, cached = self.arena.alloc_prefill(seq_id, np.asarray(inputs["tokens"])[0])
        else:
            self.arena.alloc(seq_id, t_in)  # no content hash for raw embeds
            cached = 0
        try:
            if cached >= t_in:
                logits = self._frozen_first_token(seq_id, inputs, t_in)
            else:
                logits, caches, _ = self.prefill(inputs)
                self.arena.write_prefill(seq_id, caches, t_in)
            self.arena.commit_prefill(seq_id)
        except BaseException:
            self.arena.free(seq_id)
            raise
        return logits, t_in

    def _frozen_first_token(self, seq_id, inputs: dict, t_in: int):
        """First-token logits for a whole-prompt prefix-cache hit: every
        page is already resident, so ONE frozen (no-KV-write) decode step at
        position t_in - 1 reads them back — nothing shared is touched."""
        row = self.arena.block_row(seq_id, self.block_width)
        last = np.asarray(inputs["tokens"])[:, -1:].astype(np.int32)
        return self.paged_decode_step(
            last, np.asarray([t_in - 1], np.int32), row[None, :], write_kv=False
        )

    def begin_prefill_paged(self, seq_id, inputs: dict) -> "PagedPrefillJob":
        """Allocate pages for a token prompt (through the shared-prefix
        cache) and return a chunked-prefill cursor — drive it with
        :meth:`prefill_chunk_paged` between decode steps. The cursor starts
        past any cached prefix."""
        assert self.arena is not None, "enable_paging first"
        tokens = np.asarray(inputs["tokens"])[0].astype(np.int32)
        _, cached = self.arena.alloc_prefill(seq_id, tokens)
        return PagedPrefillJob(seq_id=seq_id, tokens=tokens, pos=int(cached))

    def prefill_chunk_paged(self, job: "PagedPrefillJob", max_tokens: int):
        """Advance a chunked prefill by up to ``max_tokens`` prompt tokens:
        one chain invocation scatters the chunk's KV into the job's pages
        and attends causally from the chunk's start offset. Returns the
        first-token logits (1, V) once the prompt is fully processed, else
        None. The chunk buffer is padded to the next power of two (the real
        count rides in ``chunk_valid``) so the compile cache sees O(log
        max_len) chunk programs, not one per length."""
        assert self.arena is not None, "enable_paging first"
        t_in = job.t_in
        if job.pos >= t_in:  # whole-prompt hit: nothing to compute
            logits = self._frozen_first_token(
                job.seq_id, {"tokens": job.tokens[None, :]}, t_in
            )
            self.arena.commit_prefill(job.seq_id)
            return logits
        phase = self.platform.tracer.phase
        with phase("engine.prefill_chunk"):
            c = max(1, min(int(max_tokens), t_in - job.pos))
            with phase("engine.caches"):
                padded = 1 << (c - 1).bit_length()
                buf = np.zeros((1, padded), np.int32)
                buf[0, :c] = job.tokens[job.pos : job.pos + c]
                row = self.arena.block_row(job.seq_id, self.block_width)
                caches = self.paged_caches(row[None, :])
                caches["chunk_valid"] = jnp.asarray([c], jnp.int32)
                args = ({"tokens": jnp.asarray(buf)}, jnp.asarray([job.pos], jnp.int32), caches)
            self.platform.handler.note_demand(self.entry)
            logits, caches = self.platform._invoke_with_retry(self.entry, args)
            self._store_back(caches)
        job.pos += c
        if job.pos >= t_in:
            self.arena.commit_prefill(job.seq_id)
            return logits
        return None

    def paged_caches(self, block_table) -> dict:
        """Assemble the decode ``caches`` pytree for a batch served from the
        arena: the block table plus every stage's live page pool."""
        assert self.arena is not None, "enable_paging first"
        caches = {"block_table": jnp.asarray(block_table, jnp.int32)}
        for name, stage in self.arena.data.items():
            caches[name] = stage
        return caches

    def paged_decode_step(self, tokens, cur_len, block_table, *, write_kv: bool = True):
        """One decode step for a batch whose caches live in the arena.
        tokens: (B, 1); cur_len: (B,) — ragged per-request lengths;
        block_table: (B, width). The updated page pools are stored back so
        the arena always holds the latest state.

        ``write_kv=False`` runs the FROZEN variant (shared-prefix whole-hit
        admission): the step reads pages but writes nothing and no state is
        stored back. The marker rides in the caches pytree, so the frozen
        step compiles as its own program.

        Dispatches through the no-canary path: ``invoke`` would retain the
        step's args — the ENTIRE arena pytree — as the merge health-check
        canary, pinning a stale full copy of the pool between steps and
        doubling the very RAM paging exists to save. Merge health checks
        still have canaries from the (dense) prefill invocations; demand is
        noted so the fusion policy sees serve traffic as client load."""
        TRACER.note_decode_step()
        phase = self.platform.tracer.phase
        with phase("engine.decode_step"):
            self.platform.handler.note_demand(self.entry)
            with phase("engine.caches"):
                caches = self.paged_caches(block_table)
                if not write_kv:
                    caches["__frozen__"] = ()
                args = ({"tokens": tokens}, jnp.asarray(cur_len, jnp.int32), caches)
            logits, caches = self.platform._invoke_with_retry(self.entry, args)
            if write_kv:
                self._store_back(caches)
        return logits

    def _store_back(self, caches: dict) -> None:
        """Keep the step's updated page pools as the arena's state, and its
        expert row counts as :attr:`expert_rows`."""
        with self.platform.tracer.phase("engine.store_back"):
            for name in self.arena.data:
                self.arena.swap_data(name, caches[name])
            rows = tuple(caches[k] for k in (f"{n}.{tfm.EXPERT_ROWS}" for n in self.arena.data)
                         if k in caches)
            self.expert_rows = rows or None

    def _block_table_for(self, seq_ids) -> np.ndarray:
        rows = [self.arena.block_row(s, self.block_width) for s in seq_ids]
        return np.stack(rows)

    # ------------------------------------------------------------ serving API

    def prefill(self, inputs: dict, caches=None):
        b = jax.tree.leaves(inputs)[0].shape[0]
        if caches is None:
            caches = self.empty_caches(b)
        if self.cfg.family == "audio":
            t = jnp.zeros((b,), jnp.int32)
            logits, caches = self.platform.invoke(self.entry, inputs, t, {"self": caches["self"]})
            cur_len = jnp.ones((b,), jnp.int32)
        else:
            t_in = inputs["tokens"].shape[1] if "tokens" in inputs else inputs["embeds"].shape[1]
            cur_len = jnp.full((b,), t_in, jnp.int32)
            logits, caches = self.platform.invoke(self.entry, inputs, cur_len, caches)
        return logits, caches, cur_len

    def decode_step(self, tokens, cur_len, caches):
        TRACER.note_decode_step()
        if self.cfg.family == "audio":
            return self.platform.invoke(self.dec_name, tokens, cur_len, caches)
        inputs = {"tokens": tokens}
        return self.platform.invoke(self.entry, inputs, cur_len, caches)

    def decode_step_async(self, tokens, cur_len, caches):
        """Scheduled decode step: returns a Future of (logits, caches).
        Concurrent clients decoding with the same shapes coalesce into one
        micro-batched execution on the (possibly fused) chain."""
        if self.cfg.family == "audio":
            return self.platform.invoke_async(self.dec_name, tokens, cur_len, caches)
        return self.platform.invoke_async(self.entry, {"tokens": tokens}, cur_len, caches)

    def generate(self, inputs: dict, steps: int):
        """Greedy generation; returns (tokens (B, steps), per-token seconds)."""
        import time

        logits, caches, cur_len = self.prefill(inputs)
        tokens = _greedy_token(jnp.asarray(logits))
        out = [tokens]
        lat = []
        for _ in range(steps - 1):
            t0 = time.perf_counter()
            logits, caches = self.decode_step(tokens, cur_len, caches)
            lat.append(time.perf_counter() - t0)
            cur_len = cur_len + 1
            tokens = _greedy_token(jnp.asarray(logits))
            out.append(tokens)
        return jnp.concatenate(out, axis=1), lat

    def generate_paged(self, inputs: dict, steps: int):
        """Greedy generation served from the KV arena — same outputs as
        :meth:`generate`, bit for bit (the gathered page view is the same
        width as the dense cache and masked positions contribute exact
        zeros), but decode reads/writes shared pages instead of per-client
        dense cache pytrees. Pages are freed on exit."""
        import time

        assert self.arena is not None, "enable_paging first"
        b = jax.tree.leaves(inputs)[0].shape[0]
        seq_ids = [("gen", id(inputs), i) for i in range(b)]
        # dense prefill ONCE for the whole batch, then scatter each row's
        # built cache into its pages (copy-on-prefill)
        logits, caches, cur_len = self.prefill(inputs)
        t_in = int(np.asarray(cur_len)[0])
        try:
            for i, sid in enumerate(seq_ids):
                self.arena.alloc(sid, t_in)
                row = {k: jax.tree.map(lambda a: a[:, i : i + 1], v) for k, v in caches.items()}
                self.arena.write_prefill(sid, row, t_in)
            del caches
            tokens = _greedy_token(jnp.asarray(logits))
            out = [tokens]
            lat = []
            cur = np.full((b,), t_in, np.int64)
            for _ in range(steps - 1):
                t0 = time.perf_counter()
                for sid, c in zip(seq_ids, cur):
                    self.arena.extend(sid, int(c) + 1)  # page for the write position
                bt = self._block_table_for(seq_ids)
                logits = self.paged_decode_step(tokens, cur.astype(np.int32), bt)
                lat.append(time.perf_counter() - t0)
                cur += 1
                tokens = _greedy_token(jnp.asarray(logits))
                out.append(tokens)
            return jnp.concatenate(out, axis=1), lat
        finally:
            for sid in seq_ids:
                self.arena.free(sid)
