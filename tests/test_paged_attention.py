"""Paged-attention decode kernel: equivalence against the pure-jnp gather
reference AND the model's dense ``decode_attention``, across mixed
lengths, GQA group sizes, sliding windows and pool layers; the in-place
write of the step's K/V into the stacked pool; plus the block-size pin
that keeps allocator pages equal to kernel kv tiles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:
    from tests._hypothesis_fallback import given, settings, st

from repro.kernels.paged_attention.ops import (paged_attention_decode,
                                               resolve_impl)
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.serve.kv_cache import BLOCK_TOKENS, PagedKVCache


def _mk(seed, b, hq, hkv, d, n_pages, bt, nb, lengths, n_layers=2):
    """q, the step's new k/v, stacked (L, hkv, P, d, bt) pools, tables
    and lengths."""
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (b, hq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, d), jnp.float32)
    pages = (n_layers, hkv, n_pages, d, bt)
    kp = jax.random.normal(ks[3], pages, jnp.float32)
    vp = jax.random.normal(ks[4], pages, jnp.float32)
    # distinct pages per row, shuffled so table order != page order
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n_pages)[: b * nb].reshape(b, nb)
    bt_m = jnp.asarray(perm, jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32)
    return q, k, v, kp, vp, bt_m, lens


def _assert_same(got, want):
    """(out, k_pages, v_pages) of two impls: outputs close, pools equal."""
    np.testing.assert_allclose(got[0], want[0], atol=3e-6)
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_array_equal(got[2], want[2])


def _dense(pages, layer, bt_m):
    """(L, hkv, P, d, bt) pool -> the layer's dense (b, S, hkv, d) cache:
    table order is position order per the block-table ABI."""
    b, nb = bt_m.shape
    _, hkv, _, d, bt = pages.shape
    return np.asarray(pages)[layer][:, np.asarray(bt_m)] \
        .transpose(1, 2, 4, 0, 3).reshape(b, nb * bt, hkv, d)


# ---------------------------------------------------------------------------
# kernel == gather ref
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["kernel", "ref"])
@pytest.mark.parametrize("b,hq,hkv,d,bt,nb,window", [
    (4, 4, 2, 16, 8, 3, 0),      # GQA g=2, mixed lengths
    (3, 6, 3, 32, 16, 2, 0),     # g=2, wider head
    (2, 4, 4, 16, 8, 4, 0),      # MHA g=1
    (4, 8, 2, 16, 8, 3, 6),      # sliding window inside one page
    (3, 4, 1, 16, 8, 4, 20),     # window spanning pages, g=4
])
def test_paged_matches_ref(impl, b, hq, hkv, d, bt, nb, window):
    n_pages = b * nb + 1
    lengths = [(i * 7 + 3) % (nb * bt) + 1 for i in range(b)]
    lengths[0] = nb * bt             # one full row
    q, k, v, kp, vp, bt_m, lens = _mk(b + d, b, hq, hkv, d, n_pages, bt, nb,
                                      lengths)
    out = paged_attention_decode(q, k, v, kp, vp, 1, bt_m, lens,
                                 window=window, impl=impl, interpret=True)
    ref = paged_attention_ref(q, k, v, kp, vp, 1, bt_m, lens, window=window)
    _assert_same(out, ref)


def test_inactive_rows_output_exact_zeros():
    q, k, v, kp, vp, bt_m, lens = _mk(1, 4, 4, 2, 16, 13, 8, 3,
                                      [0, 5, 0, 17])
    for impl in ("kernel", "ref"):
        out, _, _ = paged_attention_decode(q, k, v, kp, vp, 0, bt_m, lens,
                                           impl=impl, interpret=True)
        assert np.all(np.asarray(out)[[0, 2]] == 0.0), impl
        assert np.all(np.isfinite(np.asarray(out)))


@pytest.mark.parametrize("b,window,lengths", [
    (8, 0, [0, 0, 19, 0, 0, 0, 7, 0]),        # most rows inactive
    (4, 0, [9, 1, 17, 24]),                   # pages past each row's last
    (3, 12, [30, 26, 32]),                    # window opens in page 3
], ids=["most_inactive", "past_last", "window_third_page"])
def test_pages_outside_the_span_are_never_read(b, window, lengths):
    """Every page a row cannot attend to — past its last page, before its
    window's first, or any page of an inactive row — holds NaN in every
    layer: the kernel's output is still the reference's over finite
    pages, and its pools the reference's write into the poisoned ones."""
    hq, hkv, d, bt, nb, layer = 4, 2, 16, 8, 4, 1
    q, k, v, kp, vp, bt_m, lens = _mk(17 + b, b, hq, hkv, d, b * nb + 1, bt,
                                      nb, lengths)
    tables = np.asarray(bt_m)
    dead = [tables[r, p] for r, n in enumerate(lengths) for p in range(nb)
            if not (max(n - window, 0) // bt if window else 0)
            <= p < -(-n // bt)]
    assert dead
    kp_nan = kp.at[:, :, np.asarray(dead)].set(jnp.nan)
    vp_nan = vp.at[:, :, np.asarray(dead)].set(jnp.nan)
    out, ko, vo = paged_attention_decode(q, k, v, kp_nan, vp_nan, layer,
                                         bt_m, lens, window=window,
                                         impl="kernel", interpret=True)
    want, _, _ = paged_attention_ref(q, k, v, kp, vp, layer, bt_m, lens,
                                     window=window)
    _, want_k, want_v = paged_attention_ref(q, k, v, kp_nan, vp_nan, layer,
                                            bt_m, lens, window=window)
    assert np.all(np.isfinite(np.asarray(out)))
    np.testing.assert_allclose(out, want, atol=3e-6)
    np.testing.assert_array_equal(ko, want_k)
    np.testing.assert_array_equal(vo, want_v)
    inactive = [r for r, n in enumerate(lengths) if n == 0]
    assert np.all(np.asarray(out)[inactive] == 0.0)


@pytest.mark.parametrize("impl", ["kernel", "ref"])
@pytest.mark.parametrize("layer", [0, 1, 2])
def test_layer_index_picks_its_layer(impl, layer):
    """The layer index alone chooses which layer of the stacked pool is
    read and written: the same as handing the call that layer alone."""
    q, k, v, kp, vp, bt_m, lens = _mk(3, 3, 4, 2, 16, 10, 8, 3,
                                      [20, 9, 1], n_layers=3)
    out, ko, vo = paged_attention_decode(q, k, v, kp, vp, layer, bt_m, lens,
                                         impl=impl, interpret=True)
    one = slice(layer, layer + 1)
    alone = paged_attention_ref(q, k, v, kp[one], vp[one], 0, bt_m, lens)
    np.testing.assert_allclose(out, alone[0], atol=3e-6)
    np.testing.assert_array_equal(ko[one], alone[1])
    np.testing.assert_array_equal(vo[one], alone[2])
    for other in {0, 1, 2} - {layer}:
        np.testing.assert_array_equal(ko[other], kp[other])
        np.testing.assert_array_equal(vo[other], vp[other])
        o_other, _, _ = paged_attention_ref(q, k, v, kp, vp, other, bt_m,
                                            lens)
        assert not np.allclose(out, o_other, atol=1e-3)


def test_in_place_write_touches_only_the_new_columns():
    """The kernel writes row r's new K/V at (layer, ih, its page, :, its
    column) for active rows and nowhere else, and attends over it: the
    stale column it overwrites is poisoned, so reading it would show."""
    b, hq, hkv, d, bt, nb, layer = 4, 4, 2, 16, 8, 3, 1
    lengths = [17, 0, 8, 1]              # mid-page, inactive, page end, first
    q, k, v, kp, vp, bt_m, lens = _mk(11, b, hq, hkv, d, b * nb + 1, bt, nb,
                                      lengths)
    tables, lens_np = np.asarray(bt_m), np.asarray(lens)
    active = [r for r in range(b) if lens_np[r] > 0]
    page = {r: tables[r, (lens_np[r] - 1) // bt] for r in active}
    off = {r: (lens_np[r] - 1) % bt for r in active}
    for r in active:
        kp = kp.at[layer, :, page[r], :, off[r]].set(1e4)
        vp = vp.at[layer, :, page[r], :, off[r]].set(1e4)

    out, ko, vo = paged_attention_decode(q, k, v, kp, vp, layer, bt_m, lens,
                                         impl="kernel", interpret=True)
    _assert_same((out, ko, vo),
                 paged_attention_ref(q, k, v, kp, vp, layer, bt_m, lens))

    want_k, want_v = np.array(kp), np.array(vp)
    for r in active:
        want_k[layer, :, page[r], :, off[r]] = np.asarray(k)[r]
        want_v[layer, :, page[r], :, off[r]] = np.asarray(v)[r]
    np.testing.assert_array_equal(ko, want_k)
    np.testing.assert_array_equal(vo, want_v)
    # the inactive row's pages are untouched
    assert np.array_equal(np.asarray(ko)[:, :, tables[1]],
                          np.asarray(kp)[:, :, tables[1]])

    from repro.models.attention import decode_attention
    dense = decode_attention(
        q[:, None], jnp.asarray(_dense(want_k, layer, bt_m)),
        jnp.asarray(_dense(want_v, layer, bt_m)), lens)[:, 0]
    np.testing.assert_allclose(np.asarray(out)[active],
                               np.asarray(dense)[active], atol=3e-6)


# ---------------------------------------------------------------------------
# kernel == the model's dense decode_attention (the serve-path oracle)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["kernel", "ref"])
def test_paged_matches_dense_decode_attention(impl):
    from repro.models.attention import decode_attention

    b, hq, hkv, d, bt, nb = 3, 4, 2, 16, 8, 3
    lengths = [24, 9, 1]
    q, k, v, kp, vp, bt_m, lens = _mk(5, b, hq, hkv, d, b * nb, bt, nb,
                                      lengths)
    out, ko, vo = paged_attention_decode(q, k, v, kp, vp, 1, bt_m, lens,
                                         impl=impl, interpret=True)
    dense = decode_attention(q[:, None], jnp.asarray(_dense(ko, 1, bt_m)),
                             jnp.asarray(_dense(vo, 1, bt_m)), lens)[:, 0]
    np.testing.assert_allclose(out, dense, atol=3e-6)


# ---------------------------------------------------------------------------
# properties: mixed lengths x GQA x window, kernel == ref
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**16), g=st.sampled_from([1, 2, 4]),
       window=st.sampled_from([0, 5, 16]), bt=st.sampled_from([8, 16]))
def test_paged_attention_property(seed, g, window, bt):
    rng = np.random.default_rng(seed)
    b, hkv, d, nb = 4, 2, 16, 2
    hq = g * hkv
    lengths = rng.integers(0, nb * bt + 1, b).tolist()
    q, k, v, kp, vp, bt_m, lens = _mk(seed, b, hq, hkv, d, b * nb + 1, bt,
                                      nb, lengths)
    out = paged_attention_decode(q, k, v, kp, vp, seed % 2, bt_m, lens,
                                 window=window, impl="kernel", interpret=True)
    ref = paged_attention_ref(q, k, v, kp, vp, seed % 2, bt_m, lens,
                              window=window)
    _assert_same(out, ref)


# ---------------------------------------------------------------------------
# pins
# ---------------------------------------------------------------------------

def test_kernel_kv_tile_pins_to_allocator_block_size():
    """Allocator pages ARE kernel kv tiles: the kernel reads the page size
    off the pool's lane axis, so the batched executor's pool, its
    allocator and the default page size must agree."""
    from repro.configs import get_smoke
    from repro.serve.batched_executor import JaxBatchedExecutor

    ex = JaxBatchedExecutor(get_smoke("smollm-135m"), 12, 2)
    assert ex._kp.shape[-1] == ex._vp.shape[-1] == BLOCK_TOKENS
    assert ex.block_tokens == ex.kv.block_tokens == BLOCK_TOKENS
    assert PagedKVCache(1).block_tokens == BLOCK_TOKENS


def test_resolve_impl_off_tpu_is_ref():
    assert resolve_impl("kernel") == "kernel"
    assert resolve_impl("ref") == "ref"
    if jax.default_backend() != "tpu":
        assert resolve_impl("auto") == "ref"
