"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.ssd_scan import ssd_scan

RNG = jax.random.PRNGKey(7)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("b,t,h,kv,hd", [
    (2, 256, 4, 2, 64),
    (1, 128, 8, 8, 32),
    (2, 256, 4, 1, 64),   # MQA
    (1, 512, 2, 2, 128),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_vs_ref(b, t, h, kv, hd, causal, dtype):
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (b, t, h, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, t, kv, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, t, kv, hd), jnp.float32).astype(dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64, interpret=True)
    expect = ref.mha_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(expect, np.float32), **tol(dtype))


@pytest.mark.parametrize("b,s,h,kv,hd", [(2, 512, 4, 2, 64), (3, 1024, 8, 1, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_vs_ref(b, s, h, kv, hd, dtype):
    ks = jax.random.split(RNG, 4)
    q = jax.random.normal(ks[0], (b, h, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, kv, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, kv, hd), jnp.float32).astype(dtype)
    cur = jax.random.randint(ks[3], (b,), 1, s, jnp.int32)
    out = decode_attention(q, k, v, cur, block_k=256, interpret=True)
    expect = ref.decode_attn_ref(q, k, v, cur)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(expect, np.float32), **tol(dtype))


@pytest.mark.parametrize("b,pages,page,h,kv,hd,width,ppb,lengths", [
    pytest.param(3, 9, 128, 4, 2, 64, 3, None, None, id="3-9-128-4-2-64"),
    pytest.param(2, 5, 256, 8, 1, 32, 3, None, None, id="2-5-256-8-1-32"),  # MQA
    # four 32-key blocks, the last partial; a one-page and a one-block sequence
    pytest.param(3, 17, 16, 4, 2, 64, 8, 2, (117, 45, 3), id="multi-block-partial-last"),
    # a length on a page boundary (3 pages) and one on a block boundary (2 blocks)
    pytest.param(2, 17, 16, 4, 2, 64, 8, 2, (48, 64), id="page-and-block-boundary"),
    pytest.param(1, 9, 128, 6, 2, 128, 4, None, (300,), id="one-slot-frozen-step"),
    pytest.param(2, 9, 16, 4, 4, 32, 4, 2, (50, 17), id="mha"),  # G = 1
    # 16-token pages, a 96-page table: 48 pages (768 keys) per block
    pytest.param(2, 97, 16, 4, 2, 64, 96, None, (1400, 17), id="page16-wide-table"),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention_vs_contiguous(b, pages, page, h, kv, hd, width, ppb, lengths, dtype):
    """Block-table-indirect kernel == gather-to-contiguous + dense oracle,
    over one or several DMA blocks of ``ppb`` pages (derived when None)."""
    from repro.kernels.paged_attention import gather_pages, paged_decode_attention

    ks = jax.random.split(RNG, 5)
    q = jax.random.normal(ks[0], (b, h, hd), jnp.float32).astype(dtype)
    kp = jax.random.normal(ks[1], (pages, kv, page, hd), jnp.float32).astype(dtype)
    vp = jax.random.normal(ks[2], (pages, kv, page, hd), jnp.float32).astype(dtype)
    # ragged sequences through a shuffled table; padded rows hit page 0
    bt = jax.random.randint(ks[3], (b, width), 1, pages, jnp.int32)
    if lengths is None:
        cur = jax.random.randint(ks[4], (b,), 1, width * page, jnp.int32)
    else:
        cur = jnp.array(lengths, jnp.int32)
    out = paged_decode_attention(q, kp, vp, bt, cur, interpret=True, pages_per_block=ppb)
    expect = ref.decode_attn_ref(q, gather_pages(kp, bt), gather_pages(vp, bt), cur)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(expect, np.float32), **tol(dtype))


def test_paged_decode_attention_scratch_rows_masked():
    """Masked slots (all-scratch rows, cur_len 0) must produce EXACT zeros
    — not a mean of scratch-page garbage — and not disturb live rows: an
    8-slot batch with 2 live slots, as the batcher sends its capacity."""
    from repro.kernels.paged_attention import gather_pages, paged_decode_attention

    ks = jax.random.split(RNG, 3)
    b, pages, page, h, kv, hd = 8, 5, 128, 2, 2, 32
    q = jax.random.normal(ks[0], (b, h, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (pages, kv, page, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (pages, kv, page, hd), jnp.float32)
    live = np.array([2, 5])
    bt = jnp.zeros((b, 2), jnp.int32).at[live].set(jnp.array([[1, 2], [4, 3]], jnp.int32))
    cur = jnp.zeros((b,), jnp.int32).at[live].set(jnp.array([page + 7, 2 * page], jnp.int32))
    out = paged_decode_attention(q, kp, vp, bt, cur, interpret=True)
    empty = np.setdiff1d(np.arange(b), live)
    np.testing.assert_array_equal(np.asarray(out)[empty], 0.0)
    expect = ref.decode_attn_ref(
        q[live], gather_pages(kp, bt[live]), gather_pages(vp, bt[live]), cur[live]
    )
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(expect), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("lengths,window,ppb", [
    pytest.param((117, 45, 3, 0), 20, 2, id="window-inside-one-block"),
    pytest.param((117, 45, 3, 0), 40, 2, id="window-across-blocks"),
    pytest.param((120, 64, 17, 0), 5, 1, id="window-inside-one-page"),
    pytest.param((128, 100, 33, 0), 16, 2, id="window-on-page-boundary"),
    pytest.param((90, 31, 7, 0), 1000, None, id="window-longer-than-every-slot"),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention_window(lengths, window, ppb, dtype):
    """Slot b attends to keys [max(0, len - W), len) only: the kernel equals
    the windowed oracle on the gathered cache, and an empty slot is zero."""
    from repro.kernels.paged_attention import gather_pages, paged_decode_attention

    b, pages, page, h, kv, hd, width = len(lengths), 17, 16, 4, 2, 64, 8
    ks = jax.random.split(RNG, 4)
    q = jax.random.normal(ks[0], (b, h, hd), jnp.float32).astype(dtype)
    kp = jax.random.normal(ks[1], (pages, kv, page, hd), jnp.float32).astype(dtype)
    vp = jax.random.normal(ks[2], (pages, kv, page, hd), jnp.float32).astype(dtype)
    bt = jax.random.randint(ks[3], (b, width), 1, pages, jnp.int32).at[-1].set(0)
    cur, win = jnp.array(lengths, jnp.int32), jnp.array([window], jnp.int32)
    out = paged_decode_attention(q, kp, vp, bt, cur, win, interpret=True, pages_per_block=ppb)
    expect = ref.decode_attn_ref(q, gather_pages(kp, bt), gather_pages(vp, bt), cur, win)
    np.testing.assert_allclose(np.asarray(out[:-1], np.float32),
                               np.asarray(expect[:-1], np.float32), **tol(dtype))
    np.testing.assert_array_equal(np.asarray(out)[-1], 0.0)  # the empty slot
    # keys before the window carry no weight: changing them changes nothing
    lo = max(0, lengths[0] - window)
    if lo:
        keys = gather_pages(kp, bt[:1])
        poked = keys.at[:, :lo].set(99.0)
        again = ref.decode_attn_ref(q[:1], poked, gather_pages(vp, bt[:1]), cur[:1], win)
        np.testing.assert_array_equal(np.asarray(again), np.asarray(expect[:1]))


@pytest.mark.parametrize("cur,window,ppb", [(100, 20, 1), (100, 40, 2), (64, 32, 1)])
def test_paged_decode_window_never_reads_the_pages_before_it(cur, window, ppb):
    """The pages wholly before the window hold NaN: a kernel that folded
    them in, masked or not, would return NaN. The output is the same bits
    as with those pages intact."""
    from repro.kernels.paged_attention import paged_decode_attention

    pages, page, h, kv, hd = 9, 16, 4, 2, 64
    ks = jax.random.split(RNG, 4)
    q = jax.random.normal(ks[0], (1, h, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (pages, kv, page, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (pages, kv, page, hd), jnp.float32)
    bt = jax.random.permutation(ks[3], jnp.arange(1, pages, dtype=jnp.int32))[None]
    before = bt[0, : (cur - window) // page]  # physical pages wholly below the window
    assert before.size
    args = (bt, jnp.array([cur], jnp.int32), jnp.array([window], jnp.int32))
    clean = paged_decode_attention(q, kp, vp, *args, interpret=True, pages_per_block=ppb)
    poisoned = paged_decode_attention(q, kp.at[before].set(jnp.nan), vp.at[before].set(jnp.nan),
                                      *args, interpret=True, pages_per_block=ppb)
    assert np.isfinite(np.asarray(poisoned)).all()
    np.testing.assert_array_equal(np.asarray(poisoned), np.asarray(clean))


@pytest.mark.parametrize("kernel", ["decode", "chunk"])
def test_paged_kernels_without_a_window_equal_an_unbounded_one_bit_for_bit(kernel):
    """No window compiles the unwindowed kernel; a window wider than every
    sequence (a full layer of a windowed model) computes the same bits."""
    from repro.kernels.paged_attention import paged_chunk_attention, paged_decode_attention
    from repro.models.attention import NO_WINDOW

    ks = jax.random.split(RNG, 4)
    pages, page, h, kv, hd = 17, 16, 4, 2, 64
    kp = jax.random.normal(ks[1], (pages, kv, page, hd), jnp.float32).astype(jnp.bfloat16)
    vp = jax.random.normal(ks[2], (pages, kv, page, hd), jnp.float32).astype(jnp.bfloat16)
    wide = jnp.array([NO_WINDOW], jnp.int32)
    if kernel == "decode":
        q = jax.random.normal(ks[0], (3, h, hd), jnp.float32).astype(jnp.bfloat16)
        bt = jax.random.randint(ks[3], (3, 8), 1, pages, jnp.int32)
        args = (q, kp, vp, bt, jnp.array([117, 45, 0], jnp.int32))
        run = lambda *w: paged_decode_attention(*args, *w, interpret=True, pages_per_block=2)
    else:
        q = jax.random.normal(ks[0], (1, 32, h, hd), jnp.float32).astype(jnp.bfloat16)
        bt = jax.random.permutation(ks[3], jnp.arange(1, pages, dtype=jnp.int32))[None, :8]
        args = (q, kp, vp, bt, jnp.array([37], jnp.int32))
        run = lambda *w: paged_chunk_attention(*args, *w, block_q=16, interpret=True)
    np.testing.assert_array_equal(np.asarray(run()), np.asarray(run(wide)))


@pytest.mark.parametrize("c,start,window,block_q", [
    (32, 37, 16, 16),    # two q blocks; the window starts mid-page
    (16, 100, 40, 16),   # the window reaches back across pages
    (32, 0, 8, 16),      # a prompt's first chunk
    (16, 50, 1, 16),     # each row sees only itself
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_chunk_attention_window(c, start, window, block_q, dtype):
    """Row p attends to keys (p - W, p]: the chunk kernel equals the
    windowed causal oracle with the chunk's offset."""
    from repro.kernels.paged_attention import gather_pages, paged_chunk_attention

    ks = jax.random.split(RNG, 4)
    pages, page, h, kv, hd, width = 17, 16, 4, 2, 64, 12
    q = jax.random.normal(ks[0], (1, c, h, hd), jnp.float32).astype(dtype)
    kp = jax.random.normal(ks[1], (pages, kv, page, hd), jnp.float32).astype(dtype)
    vp = jax.random.normal(ks[2], (pages, kv, page, hd), jnp.float32).astype(dtype)
    bt = jax.random.permutation(ks[3], jnp.arange(1, pages, dtype=jnp.int32))[None, :width]
    st, win = jnp.array([start], jnp.int32), jnp.array([window], jnp.int32)
    out = paged_chunk_attention(q, kp, vp, bt, st, win, block_q=block_q, interpret=True)
    expect = ref.chunked_mha_ref(q, gather_pages(kp, bt), gather_pages(vp, bt), causal=True,
                                 q_offset=start, window=win)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(expect, np.float32), **tol(dtype))


@pytest.mark.parametrize("cur,window", [(1, 1024), (1024, 1024), (1025, 1024), (3000, 1024),
                                        (9000, 1024), (300, 5)])
def test_decode_page_loop_starts_at_the_windows_first_page(cur, window):
    """The decode kernel copies pages [first page, live pages) only: the
    first holds the window's lowest key, every page before it lies wholly
    below the window, and its loop starts at that page's block."""
    from repro.kernels.paged_attention import decode_span

    page, ppb = 128, 8
    lo, first_page, first_blk = (int(v) for v in decode_span(jnp.int32(cur), window, page, ppb))
    assert lo == max(0, cur - window)
    assert first_page * page <= lo < (first_page + 1) * page
    assert first_blk == first_page // ppb


@pytest.mark.parametrize("row0,window", [(0, 16), (37, 16), (100, 40), (64, 1), (5, 1 << 30)])
def test_chunk_page_map_issues_no_dma_before_the_window(row0, window):
    """For a q block, the pages its grid steps map to run from the page of
    its first row's lowest key to the page of its last row: a page wholly
    before the window maps to the first page already fetched."""
    from repro.kernels.paged_attention import chunk_pages

    page, block_q, n = 16, 16, 12
    first, last = (int(v) for v in chunk_pages(jnp.int32(row0), block_q, page, jnp.int32(window)))
    low = max(0, row0 - window + 1)
    assert first == low // page and last == (row0 + block_q - 1) // page
    mapped = [min(max(ik, first), last) for ik in range(n)]
    assert set(mapped) == set(range(first, last + 1))
    assert all((p + 1) * page > low for p in mapped)


@pytest.mark.parametrize("c,start,page,h,kv,hd,block_q", [
    (16, 0, 128, 4, 2, 64, 128),     # first chunk of a prompt
    (32, 200, 128, 4, 2, 64, 16),    # later chunk, two q blocks, crosses a page
    (8, 37, 16, 2, 1, 32, 128),      # MQA, small pages, unaligned start
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_chunk_attention_vs_contiguous(c, start, page, h, kv, hd, block_q, dtype):
    """Chunked-prefill kernel == gather-to-contiguous + causal oracle with
    the chunk's absolute offset."""
    from repro.kernels.paged_attention import gather_pages, paged_chunk_attention

    ks = jax.random.split(RNG, 4)
    pages, width = 9, 4
    q = jax.random.normal(ks[0], (1, c, h, hd), jnp.float32).astype(dtype)
    kp = jax.random.normal(ks[1], (pages, kv, page, hd), jnp.float32).astype(dtype)
    vp = jax.random.normal(ks[2], (pages, kv, page, hd), jnp.float32).astype(dtype)
    bt = jax.random.permutation(ks[3], jnp.arange(1, pages, dtype=jnp.int32))[None, :width]
    out = paged_chunk_attention(q, kp, vp, bt, jnp.array([start], jnp.int32),
                                block_q=block_q, interpret=True)
    expect = ref.mha_ref(q, gather_pages(kp, bt), gather_pages(vp, bt), causal=True,
                         q_offset=start)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(expect, np.float32), **tol(dtype))


def test_decode_attention_length_edge_cases():
    b, s, h, kv, hd = 2, 256, 2, 2, 32
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (b, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kv, hd), jnp.float32)
    for cur in (jnp.array([1, s]), jnp.array([s, 1])):
        out = decode_attention(q, k, v, cur, block_k=128, interpret=True)
        expect = ref.decode_attn_ref(q, k, v, cur)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("prompt,needle", [
    (200, r"seq lengths \(200,200\) must divide blocks \(128,128\)"),  # dense prefill
    (256, None),                                                      # aligned: the kernel
])
def test_kernel_mode_dense_prefill_raises_instead_of_reference(monkeypatch, prompt, needle):
    """On a TPU every attention op runs its kernel: a dense prefill whose
    length the flash blocks do not divide raises with the shapes from the
    kernel wrapper, it never returns the jnp reference's answer."""
    from repro.analysis.dispatch import TRACER
    from repro.configs import get_arch, reduced_config
    from repro.kernels import ops
    from repro.models.model import build_model

    model = build_model(reduced_config(get_arch("starcoder2-3b")))
    params = jax.eval_shape(model.init, RNG)
    batch = {"tokens": jax.ShapeDtypeStruct((1, prompt), jnp.int32)}
    monkeypatch.setattr(ops, "_mode", lambda: "kernel")
    TRACER.arm()
    try:
        before = TRACER.path_counts()
        if needle is None:
            # aligned shapes trace into the flash kernel (lowering it needs a TPU)
            logits, _ = jax.eval_shape(model.prefill_fn, params, batch)
            assert logits.shape == (1, model.cfg.vocab_size)
        else:
            with pytest.raises(ValueError, match=needle):
                jax.eval_shape(model.prefill_fn, params, batch)
        after = TRACER.path_counts()
    finally:
        TRACER.disarm()
    # the op entered its kernel path, never the reference
    assert after.get(("attention", "kernel"), 0) > before.get(("attention", "kernel"), 0)
    assert after.get(("attention", "ref"), 0) == before.get(("attention", "ref"), 0)


def test_kernel_mode_dense_decode_raises_instead_of_reference(monkeypatch):
    """Same rule for the dense decode kernel: a cache width its 512-row kv
    blocks do not divide raises with the shapes."""
    from repro.kernels import ops
    from repro.models import attention as attn_mod

    monkeypatch.setattr(ops, "_mode", lambda: "kernel")
    q = jax.ShapeDtypeStruct((2, 1, 4, 128), jnp.bfloat16)
    cache = jax.ShapeDtypeStruct((2, 768, 2, 128), jnp.bfloat16)
    cur = jax.ShapeDtypeStruct((2,), jnp.int32)
    with pytest.raises(ValueError, match="S=768 must divide block_k=512"):
        jax.eval_shape(attn_mod.decode_attention, q, cache, cache, cur)


@pytest.mark.parametrize("b,t,h,grp,p,n,chunk", [
    (2, 128, 4, 1, 32, 16, 32),
    (1, 256, 2, 2, 64, 32, 64),
    (1, 64, 2, 1, 16, 8, 64),  # single chunk
])
def test_ssd_scan_vs_ref(b, t, h, grp, p, n, chunk):
    ks = jax.random.split(RNG, 5)
    x = jax.random.normal(ks[0], (b, t, h, p), jnp.float32)
    bm = jax.random.normal(ks[1], (b, t, grp, n), jnp.float32) * 0.5
    cm = jax.random.normal(ks[2], (b, t, grp, n), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, t, h), jnp.float32))
    a_log = jax.random.normal(ks[4], (h,), jnp.float32) * 0.3
    d_skip = jnp.ones((h,), jnp.float32)
    out = ssd_scan(x, bm, cm, dt, a_log, d_skip, chunk=chunk, interpret=True)
    expect, _ = ref.ssd_ref(x, bm, cm, dt, a_log, d_skip)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect, np.float32), rtol=5e-4, atol=5e-4)


def test_models_ssd_chunked_matches_oracle():
    """The model's jnp chunked SSD (the lowering path) == naive O(T^2) oracle."""
    from repro.models.ssm import ssd_chunked

    ks = jax.random.split(RNG, 5)
    b, t, h, grp, p, n = 2, 96, 4, 2, 16, 8
    x = jax.random.normal(ks[0], (b, t, h, p), jnp.float32)
    bm = jax.random.normal(ks[1], (b, t, grp, n), jnp.float32) * 0.5
    cm = jax.random.normal(ks[2], (b, t, grp, n), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, t, h), jnp.float32))
    a_log = jax.random.normal(ks[4], (h,), jnp.float32) * 0.3
    d_skip = jnp.ones((h,), jnp.float32)
    y, state = ssd_chunked(x, bm, cm, dt, a_log, d_skip, chunk=32)
    y_ref, state_ref = ref.ssd_ref(x, bm, cm, dt, a_log, d_skip)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref, np.float32), rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(state), np.asarray(state_ref), rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("e,c,d,f", [(4, 64, 128, 256), (2, 128, 64, 128), (1, 32, 32, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gmm_vs_ref(e, c, d, f, dtype):
    """Every expert with the same c rows (a capacity buffer's layout)."""
    ks = jax.random.split(RNG, 2)
    x = jax.random.normal(ks[0], (e * c, d), jnp.float32).astype(dtype)
    w = (jax.random.normal(ks[1], (e, d, f), jnp.float32) * 0.05).astype(dtype)
    sizes = jnp.full((e,), c, jnp.int32)
    out = moe_gmm(x, w, sizes, block_rows=32, interpret=True)
    expect = ref.gmm_ref(x, w, sizes)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(expect, np.float32), **tol(dtype))


@pytest.mark.parametrize("sizes", [(16, 0, 48, 16, 0), (0, 0, 0, 32, 0), (64, 16, 16, 16, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gmm_ragged_groups_vs_a_per_expert_loop(sizes, dtype):
    """Uneven groups, empty experts and unused trailing blocks: each group's
    rows times its own expert's weights; the rows past the groups are not
    read back."""
    e, d, f, bm = len(sizes), 64, 128, 16
    m = sum(sizes) + 2 * bm  # two trailing blocks in no group
    ks = jax.random.split(RNG, 2)
    x = jax.random.normal(ks[0], (m, d), jnp.float32).astype(dtype)
    w = (jax.random.normal(ks[1], (e, d, f), jnp.float32) * 0.05).astype(dtype)
    out = np.asarray(moe_gmm(x, w, jnp.array(sizes, jnp.int32), block_rows=bm, interpret=True),
                     np.float32)
    start = 0
    for j, n in enumerate(sizes):
        want = np.asarray(x[start:start + n].astype(jnp.float32) @ w[j].astype(jnp.float32))
        np.testing.assert_allclose(out[start:start + n], want, **tol(dtype))
        start += n


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("path", ["kernel", "ref"])
def test_gmm_reads_a_stacked_layer_in_place_bit_for_bit(monkeypatch, path, layer):
    """A stack of L=3 layers' experts (L, E, d, f) read at ``layer`` gives
    the bits of the layer's own (E, d, f) matrix: the kernel against itself
    on ``w[layer]``, ``ops.gmm``'s reference against ``ragged_dot`` on it.
    The groups hold an expert with no rows, and the buffer ends in blocks
    past the last group."""
    from repro.kernels import ops

    sizes = jnp.array([16, 0, 48, 16, 0], jnp.int32)
    e, d, f, bm = sizes.shape[0], 64, 128, 16
    m = int(sizes.sum()) + 2 * bm
    ks = jax.random.split(RNG, 2)
    x = jax.random.normal(ks[0], (m, d), jnp.float32).astype(jnp.bfloat16)
    w = (jax.random.normal(ks[1], (3, e, d, f), jnp.float32) * 0.05).astype(jnp.bfloat16)
    at = jnp.array([layer], jnp.int32)
    if path == "kernel":
        got = moe_gmm(x, w, sizes, at, block_rows=bm, interpret=True)
        want = moe_gmm(x, w[layer], sizes, block_rows=bm, interpret=True)
        n = int(sizes.sum())  # rows past the groups are left unwritten
        np.testing.assert_array_equal(np.asarray(got)[:n], np.asarray(want)[:n])
    else:
        monkeypatch.setattr(ops, "_mode", lambda: "ref")
        got = jax.jit(lambda x, w, s, at: ops.gmm(x, w, s, at, block_rows=bm))(x, w, sizes, at)
        want = jax.lax.ragged_dot(x, w[layer], sizes,
                                  preferred_element_type=jnp.float32).astype(x.dtype)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_moe_gmm_fetches_no_weights_of_an_expert_without_rows():
    """The weight panel each grid step maps to: only experts that have rows,
    each as one run of consecutive steps (fetched once), and the steps past
    the groups repeat the last panel (no DMA)."""
    from repro.kernels.moe_gmm import _live, block_groups

    sizes = jnp.array([32, 0, 0, 16, 0, 48, 0], jnp.int32)
    bm, nb = 16, 10
    ids, active = block_groups(sizes, bm, nb)
    panels = [int(ids[_live(i, active)]) for i in range(nb)]
    assert int(active[0]) == 6
    assert panels == [0, 0, 3, 5, 5, 5, 5, 5, 5, 5]
    assert set(panels) == {j for j, n in enumerate(np.asarray(sizes)) if n}
