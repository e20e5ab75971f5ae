"""Top-k token-choice Mixture-of-Experts: dropless on one device, with
expert parallelism when sharded.

On one device (``rules is None``: the serving path) the layer is dropless.
Routes sort by expert into a row buffer of static size in which each
expert's rows are padded to whole blocks of the grouped matmul's rows; the
three expert matmuls run as one grouped matmul each (``kernels/ops.gmm``),
which reads the weights of the experts that got rows and no others; the
outputs gather back to their routes and combine with the gate weights.
Rows marked invalid (empty batch slots, chunk padding) route nowhere.

Sharded (``rules`` given), the capacity formulation below runs.

Why not the GShard dispatch einsum: its (S, E, C) one-hot contraction costs
``N*S*k*cf*d`` FLOPs — for qwen3-moe (E=128, top-8) that is ~5x the *useful*
expert FLOPs, wrecking the MODEL_FLOPS/HLO_FLOPs roofline ratio. Instead we
use the Switch-Transformer capacity formulation with real gather/scatter:

  1. route: router logits -> top-k experts + normalized weights per token
  2. position: cumulative count per expert (capacity C, overflow dropped)
  3. dispatch: scatter token vectors into a (G, E, C, d) buffer
  4. compute: dense per-expert GEMMs (MXU-friendly; E sharded over 'model'
     = expert parallelism; weight d dim FSDP over 'data')
  5. combine: gather each token's k expert outputs, weighted sum

Tokens are processed in G groups aligned with the data-parallel sharding so
the scatter/gather stays group-local: per group XLA emits one all-gather of
the group's tokens over 'model' (the SP axis) and one reduce-scatter back —
the classic a2a-free EP schedule.

Differentiable end-to-end (indices are stop-gradient; weights flow through
softmax/top-k values). Load-balance aux loss per Switch [arXiv:2101.03961].
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.params import ParamDef
from repro.sharding.specs import LogicalRules, shard_as


def moe_defs(cfg: ModelConfig):
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    return {
        "router": ParamDef((d, e), ("embed_fsdp", None), dtype=jnp.float32),
        "wi_gate": ParamDef((e, d, f), ("experts", "embed_fsdp", "expert_ff")),
        "wi_up": ParamDef((e, d, f), ("experts", "embed_fsdp", "expert_ff")),
        "wo": ParamDef((e, f, d), ("experts", "expert_ff", "embed_fsdp")),
    }


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def capacity(n_tokens: int, cfg: ModelConfig) -> int:
    c = int(n_tokens * cfg.num_experts_per_tok * cfg.capacity_factor / cfg.num_experts)
    return max(8, _round_up(c, 8))


def num_groups(n_tokens: int, batch: int, cfg: ModelConfig, rules: LogicalRules) -> int:
    """Groups = data-parallel shard count when per-group token counts stay
    healthy (>= ~4 slots/expert); halved otherwise (tiny decode batches)."""
    target = cfg.moe_min_group_tokens or 4 * cfg.num_experts
    g = rules.mesh_axis_sizes.get("pod", 1) * rules.mesh_axis_sizes.get("data", 1)
    while g > 1 and ((n_tokens // g) < target or n_tokens % g or (g > batch and g % batch)):
        g //= 2
    return max(1, g)


def _route(params, xf: jax.Array, k: int):
    """Router probabilities (n, E), the top-k gate weights renormalised and
    their experts (n, k), in float32.

    The experts are the top k of the logits, and their gates the softmax of
    those k logits: the same as the top k of the probabilities renormalised,
    but chosen on the router matmul's output alone, so that a near tie does
    not hang on the softmax's sum, whose rounding moves with how a program
    fuses it (the aux loss's use of ``probs`` decides that)."""
    logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32), params["router"])
    top, idx = jax.lax.top_k(logits, k)
    return jax.nn.softmax(logits, axis=-1), jax.nn.softmax(top, axis=-1), jax.lax.stop_gradient(idx)


def block_rows(routes: int, num_experts: int) -> int:
    """Rows of one grouped-matmul block: the power of two nearest above an
    expert's mean rows, within [16, 128] (a bfloat16 tile's rows, the MXU's
    width)."""
    mean = -(-routes // num_experts)
    return min(128, max(16, 1 << (mean - 1).bit_length()))


def apply_moe_dropless(params, x: jax.Array, cfg: ModelConfig, valid: jax.Array | None = None,
                       layer=None):
    """x: (B, T, d); ``valid`` (B, T) bool marks the rows that route (None:
    all) -> (y (B, T, d), metrics). Every route of a valid row is computed:
    ``metrics["expert_rows"]`` (E,) int32 counts each expert's rows.

    ``layer`` (an int32 scalar) says the expert matrices are a stack's,
    (L, E, d, f), and which layer to read in place; None: one layer's."""
    from repro.kernels import ops as kops

    b, t, d = x.shape
    n = b * t
    k, e = cfg.num_experts_per_tok, cfg.num_experts
    xf = x.reshape(n, d)
    probs, w, idx = _route(params, xf, k)
    ok = jnp.ones((n,), bool) if valid is None else valid.reshape(n)
    ex = jnp.where(ok[:, None], idx, e).reshape(n * k)  # expert e: routes nowhere
    rows = jnp.zeros((e + 1,), jnp.int32).at[ex].add(1)[:e]

    # sorted row buffer: expert j's rows start at starts[j], its group
    # padded to whole blocks of bm rows; m bounds the padded groups' sum
    bm = block_rows(n * k, e)
    m = bm * (-(-(n * k) // bm) + e)
    padded = -(-rows // bm) * bm
    starts = jnp.cumsum(padded) - padded
    order = jnp.argsort(ex, stable=True)
    sorted_e = ex[order]
    first = jnp.cumsum(rows) - rows  # each expert's first place in sorted order
    je = jnp.minimum(sorted_e, e - 1)
    dest_sorted = jnp.where(sorted_e < e, starts[je] + jnp.arange(n * k) - first[je], m)
    dest = jnp.zeros((n * k,), jnp.int32).at[order].set(dest_sorted.astype(jnp.int32))
    # each buffer row's token (n: a padding row, read as zeros): only int32
    # indices scatter, and the rows move by one gather
    src = jnp.full((m,), n, jnp.int32).at[dest_sorted].set(order // k, mode="drop")
    xs = jnp.take(xf, src, axis=0, mode="fill", fill_value=0)

    at = None if layer is None else jnp.reshape(layer, (1,)).astype(jnp.int32)
    gate = kops.gmm(xs, params["wi_gate"], padded, at, block_rows=bm)
    up = kops.gmm(xs, params["wi_up"], padded, at, block_rows=bm)
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    ys = kops.gmm(h, params["wo"], padded, at, block_rows=bm)  # (m, d)

    yk = ys.at[dest].get(mode="fill", fill_value=0).reshape(n, k, d)
    wk = jnp.where(ok[:, None], w, 0.0)
    y = jnp.einsum("nkd,nk->nd", yk.astype(jnp.float32), wk).astype(x.dtype).reshape(b, t, d)

    okf = ok.astype(jnp.float32)
    n_ok = jnp.maximum(jnp.sum(okf), 1.0)
    f_e = rows.astype(jnp.float32) / n_ok
    p_e = jnp.sum(probs * okf[:, None], axis=0) / n_ok
    aux = e * jnp.sum(f_e / k * p_e)
    return y, {"moe_aux": aux, "moe_dropped": jnp.zeros((), jnp.float32), "expert_rows": rows}


def apply_moe(params, x: jax.Array, cfg: ModelConfig, rules: LogicalRules | None = None,
              valid: jax.Array | None = None, layer=None):
    """x: (B, T, d) -> (y (B, T, d), metrics dict). One device: dropless
    (``valid`` marks the rows that route, ``layer`` picks stacked experts);
    sharded: capacity buffers, on one layer's experts."""
    if rules is None:
        return apply_moe_dropless(params, x, cfg, valid, layer)
    b, t, d = x.shape
    n = b * t
    k, e = cfg.num_experts_per_tok, cfg.num_experts
    g = num_groups(n, b, cfg, rules)
    nl = n // g
    cap = capacity(nl, cfg)

    xg = x.reshape(g, nl, d)

    # --- route on the UN-reshaped (B, T, d) stream. The (g, nl, d) reshape
    # merges batch x seq and is not expressible as a block sharding, so any
    # fp32 routing math placed after it forces a full-token fp32 all-gather
    # over 'model' (measured 2 GB/op x 576 ops on qwen3 — EXPERIMENTS §Perf).
    # Routing stays SP-sharded here; only the tiny (.., k) top-k outputs get
    # reshaped into groups. ---
    x_sp = shard_as(x, ("batch", "seq", None), rules)
    logits = jnp.einsum("btd,de->bte", x_sp.astype(jnp.float32), params["router"])
    logits = shard_as(logits, ("batch", "seq", None), rules)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, k)  # (B, T, k)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    idx = jax.lax.stop_gradient(idx)
    w = w.reshape(g, nl, k)
    idx = idx.reshape(g, nl, k)

    # --- slot positions, sort-based: pos[i] = #{j <= i : e[j] == e[i]}.
    # O(N) int32 buffers (a (tokens, E) one-hot cumsum would be 4 TB here).
    e_flat = idx.reshape(g, nl * k)
    order = jnp.argsort(e_flat, axis=1, stable=True)  # (g, nl*k)
    sorted_e = jnp.take_along_axis(e_flat, order, axis=1)
    pos_in_row = jnp.broadcast_to(jnp.arange(nl * k, dtype=jnp.int32), sorted_e.shape)
    is_start = jnp.concatenate(
        [jnp.ones((g, 1), bool), sorted_e[:, 1:] != sorted_e[:, :-1]], axis=1
    )
    run_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(is_start, pos_in_row, 0), axis=1
    )
    pos_sorted = pos_in_row - run_start
    g_idx = jnp.broadcast_to(jnp.arange(g)[:, None], e_flat.shape)
    pos_flat = jnp.zeros_like(e_flat).at[g_idx, order].set(pos_sorted)
    pos_flat = jax.lax.stop_gradient(pos_flat)
    kept = pos_flat < cap
    pos_flat = jnp.where(kept, pos_flat, cap)  # cap == out-of-bounds -> dropped

    # --- dispatch: scatter tokens into (G, E, C, d) expert buffers ---
    # The group index participates in the scatter, so under plain GSPMD the
    # scattered dim-0 forces an operand ALL-GATHER (measured: ~130 GB/device
    # at qwen3 scale). shard_map over the dp axes makes the scatter
    # group-LOCAL by construction; the E-dim (expert-parallel) reshard
    # happens after, as a plain slice.
    def _dispatch_local(xg_l, e_l, pos_l):
        g_loc = xg_l.shape[0]
        xr_l = jnp.repeat(xg_l, k, axis=1)
        gi = jnp.broadcast_to(jnp.arange(g_loc)[:, None], e_l.shape)
        return jnp.zeros((g_loc, e, cap, d), xg_l.dtype).at[gi, e_l, pos_l].set(xr_l, mode="drop")

    def _combine_local(ye_l, e_l, pos_l, w_l):
        g_loc = e_l.shape[0]
        gi = jnp.broadcast_to(jnp.arange(g_loc)[:, None], e_l.shape)
        yk_l = ye_l.at[gi, e_l, pos_l].get(mode="fill", fill_value=0)  # (g_loc, nl*k, d)
        nl_l = e_l.shape[1] // k
        return jnp.sum(yk_l.reshape(g_loc, nl_l, k, d) * w_l.reshape(g_loc, nl_l, k, 1).astype(ye_l.dtype), axis=2)

    dp = rules.dp_axes() if rules is not None else ()
    dp_size = 1
    for ax in dp:
        dp_size *= rules.mesh_axis_sizes.get(ax, 1)
    use_sm = bool(dp) and rules is not None and rules.mesh is not None and g % dp_size == 0
    msize = rules.mesh_axis_sizes.get("model", 1) if rules is not None else 1
    use_ep_local = (
        cfg.moe_impl == "dropping_ep"
        and use_sm
        and msize > 1
        and e % msize == 0
    )
    if use_ep_local:
        # ---- beyond-baseline EP schedule (see EXPERIMENTS.md §Perf):
        # dispatch + combine run INSIDE shard_map over (dp, model); each
        # model shard builds/serves only ITS E/msize experts' buffers, and
        # the combine reduces partial token outputs with psum_scatter —
        # per-layer collective traffic drops from O(E*cap*d) all-gathers to
        # one token all-gather + one token reduce-scatter.
        from jax.sharding import PartitionSpec as P

        dp_spec = dp if len(dp) > 1 else dp[0]
        e_loc = e // msize
        manual = set(dp) | {"model"}
        xg_c = shard_as(xg, ("batch", None, None), rules)
        e_c = shard_as(e_flat, ("batch", None), rules)
        pos_c = shard_as(pos_flat, ("batch", None), rules)
        w_c = shard_as(w, ("batch", None, None), rules)

        def _rel(e_l, pos_l):
            e0 = jax.lax.axis_index("model") * e_loc
            rel = e_l - e0
            ok = (rel >= 0) & (rel < e_loc)
            return jnp.where(ok, rel, e_loc), jnp.where(ok, pos_l, cap)

        def disp_local(xg_l, e_l, pos_l):
            g_loc = xg_l.shape[0]
            rel, pos2 = _rel(e_l, pos_l)
            xr_l = jnp.repeat(xg_l, k, axis=1)
            gi = jnp.broadcast_to(jnp.arange(g_loc)[:, None], e_l.shape)
            return jnp.zeros((g_loc, e_loc, cap, d), xg_l.dtype).at[gi, rel, pos2].set(xr_l, mode="drop")

        xe = jax.shard_map(
            disp_local,
            mesh=rules.mesh,
            in_specs=(P(dp_spec), P(dp_spec), P(dp_spec)),
            out_specs=P(dp_spec, "model"),
            axis_names=manual,
            check_vma=False,
        )(xg_c, e_c, pos_c)
        xe = shard_as(xe, ("batch", "experts", None, None), rules)

        gate = jnp.einsum("gecd,edf->gecf", xe, params["wi_gate"])
        up = jnp.einsum("gecd,edf->gecf", xe, params["wi_up"])
        h = jax.nn.silu(gate.astype(jnp.float32)).astype(xe.dtype) * up
        ye = jnp.einsum("gecf,efd->gecd", h, params["wo"])
        ye = shard_as(ye, ("batch", "experts", None, None), rules)

        scatter_tiled = nl % msize == 0

        def comb_local(ye_l, e_l, pos_l, w_l):
            g_loc = e_l.shape[0]
            rel, pos2 = _rel(e_l, pos_l)
            gi = jnp.broadcast_to(jnp.arange(g_loc)[:, None], e_l.shape)
            yk_l = ye_l.at[gi, rel, pos2].get(mode="fill", fill_value=0)
            y_part = jnp.sum(
                yk_l.reshape(g_loc, nl, k, d) * w_l.reshape(g_loc, nl, k, 1).astype(ye_l.dtype), axis=2
            )
            if scatter_tiled:
                return jax.lax.psum_scatter(y_part, "model", scatter_dimension=1, tiled=True)
            return jax.lax.psum(y_part, "model")

        y = jax.shard_map(
            comb_local,
            mesh=rules.mesh,
            in_specs=(P(dp_spec, "model"), P(dp_spec), P(dp_spec), P(dp_spec)),
            out_specs=P(dp_spec, "model" if scatter_tiled else None),
            axis_names=manual,
            check_vma=False,
        )(ye, e_c, pos_c, w_c)
        y = shard_as(y, ("batch", "seq", None), rules)
        y = y.reshape(b, t, d)
        gia = jnp.broadcast_to(jnp.arange(g)[:, None], e_flat.shape)
        counts = jnp.zeros((g, e), jnp.float32).at[gia, e_flat].add(1.0)
        f_e = jnp.sum(counts, axis=0) / (g * nl)
        p_e = jnp.mean(probs, axis=(0, 1))
        aux = e * jnp.sum(f_e / k * p_e)
        dropped = 1.0 - jnp.mean(kept.astype(jnp.float32))
        return y, {"moe_aux": aux, "moe_dropped": dropped}
    if use_sm:
        from jax.sharding import PartitionSpec as P

        dp_spec = dp if len(dp) > 1 else dp[0]
        xg_d = shard_as(xg, ("batch", None, None), rules)
        e_d = shard_as(e_flat, ("batch", None), rules)
        pos_d = shard_as(pos_flat, ("batch", None), rules)
        xe = jax.shard_map(
            _dispatch_local,
            mesh=rules.mesh,
            in_specs=(P(dp_spec), P(dp_spec), P(dp_spec)),
            out_specs=P(dp_spec),
            axis_names=set(dp),
            check_vma=False,
        )(xg_d, e_d, pos_d)
    else:
        gi0 = jnp.broadcast_to(jnp.arange(g)[:, None], e_flat.shape)
        xr = jnp.repeat(xg, k, axis=1)
        xe = jnp.zeros((g, e, cap, d), x.dtype).at[gi0, e_flat, pos_flat].set(xr, mode="drop")
    xe = shard_as(xe, ("batch", "experts", None, None), rules)

    # --- expert compute (dense GEMMs; E is the EP axis) ---
    gate = jnp.einsum("gecd,edf->gecf", xe, params["wi_gate"])
    up = jnp.einsum("gecd,edf->gecf", xe, params["wi_up"])
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(xe.dtype) * up
    ye = jnp.einsum("gecf,efd->gecd", h, params["wo"])
    ye = shard_as(ye, ("batch", "experts", None, None), rules)

    # --- combine: one explicit all-gather of ye over 'model' (E-dim), then a
    # group-local gather + weighted sum — mirrors the dispatch ---
    ye = shard_as(ye, ("batch", None, None, None), rules)
    if use_sm:
        from jax.sharding import PartitionSpec as P

        dp_spec = dp if len(dp) > 1 else dp[0]
        w_d = shard_as(w, ("batch", None, None), rules)
        y = jax.shard_map(
            _combine_local,
            mesh=rules.mesh,
            in_specs=(P(dp_spec), P(dp_spec), P(dp_spec), P(dp_spec)),
            out_specs=P(dp_spec),
            axis_names=set(dp),
            check_vma=False,
        )(ye, e_d, pos_d, w_d)
    else:
        gi1 = jnp.broadcast_to(jnp.arange(g)[:, None], e_flat.shape)
        yk = ye.at[gi1, e_flat, pos_flat].get(mode="fill", fill_value=0)  # (g, nl*k, d)
        y = jnp.sum(yk.reshape(g, nl, k, d) * w.reshape(g, nl, k, 1).astype(ye.dtype), axis=2)
    y = shard_as(y, ("batch", None, None), rules)
    y = y.reshape(b, t, d)

    # --- Switch load-balance aux: E * sum_e f_e * P_e (counts via
    # scatter-add; no (tokens, E) one-hot materialized) ---
    gia = jnp.broadcast_to(jnp.arange(g)[:, None], e_flat.shape)
    counts = jnp.zeros((g, e), jnp.float32).at[gia, e_flat].add(1.0)
    f_e = jnp.sum(counts, axis=0) / (g * nl)
    p_e = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(f_e / k * p_e)
    dropped = 1.0 - jnp.mean(kept.astype(jnp.float32))
    metrics = {"moe_aux": aux, "moe_dropped": dropped}
    return y, metrics
