"""Storage layout of the serving arena's paged KV pools.

The paged kernels (``decode_attention.paged_*``) read one
``(block_size, W)`` tile of one head group of one page per grid step,
straight out of the stacked ``(layers, ...)`` pool.  The arena stores
every pool in exactly that layout, so XLA has nothing to convert between
the stored buffer and the kernel operand:

* **values** ``(layers, Hkv/G, pages, block_size, W)``: ``G`` KV heads
  sit side by side in the first ``G*D`` lanes of a row of ``W`` lanes,
  ``G*D`` rounded up to whole 128-lane tiles.  The TPU pads a minor
  dimension to whole tiles, and XLA answers a minor axis that would pad
  (``D = 64``, ``D = 112``, ``G*D = 576``) with a page-minor default
  layout and a relayout of the whole pool before every kernel.  A row
  that is whole tiles keeps the plain descending layout, its padding
  lanes (if any) stored explicitly.  ``G`` (``head_group``) is the
  divisor of the per-device KV head count that pads least: ``D = 64``
  packs 2 heads where the count is even, and where it is odd the divisor
  whose row pads least (9 heads: all 9 in 640 lanes); ``D = 112`` packs
  8 (896 lanes); ``D = 128`` takes ``G = 1``.
* **int8 scales** (``QuantPages.scales``) ``(layers, Hkv/G, rows, 128)``:
  one page's scales for one head group are ``G*block_size`` f32 values,
  head-major (value ``h*block_size + t``), laid along rows of 128 lanes.
  Where that segment divides 128, a row holds ``128 // (G*block_size)``
  consecutive pages; otherwise a page takes a power of two rows of its
  own (``rows_per_page``, at most 8).  Rows come in whole ``(8, 128)``
  tiles, the kernel's scale block, so a page's rows always share one.
  Both shapes are forced by how XLA treats the single-lane scatter that
  writes a token's scales: it keeps an f32 pool of ``(8, 128)`` tiles in
  place, but gives a pool with a unit second-minor axis one-row tiles,
  and flattens one whose rows are wider than 128 lanes, and either way
  relays the whole pool out around every write.

KV head ``k`` is lane block ``k % G`` of head group ``k // G``: a group
never spans devices, so the mapping is the same whether a model mesh
splits the head-group axis or not, and ``G`` is the pool's KV head count
over its head-group count wherever the arrays are seen.  Token rows land
in the stored pool through scatters whose index dimensions are the
pool's own (layer, page, offset, scale row, lane), so they update the
buffer in place.  The natural ``(..., tokens, Hkv, D)`` view
(``gather``) exists for the jnp reference path and the dense-view
fallback only.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax.numpy as jnp

from .quant import QuantPages, dequantize, quantize

LANES = 128
SUBLANES = 8        # rows of an f32 tile: a scale block
_IN_BOUNDS = "promise_in_bounds"   # block tables only name pool pages


def row_lanes(group: int, head_dim: int) -> int:
    """``W``: lanes of a value row holding ``group`` heads."""
    return -(-group * head_dim // LANES) * LANES


def head_group(head_dim: int, kv_heads: int) -> int:
    """KV heads packed into one row: the divisor of ``kv_heads`` (the
    per-device count; a model mesh splits the head-group axis) whose row
    pads least, the smallest of those."""
    best, best_fill = 1, 0.0
    for g in range(1, max(kv_heads, 1) + 1):
        fill = g * head_dim / row_lanes(g, head_dim)
        if kv_heads % g == 0 and fill > best_fill:
            best, best_fill = g, fill
    return best


def scale_rows(block_size: int, group: int) -> Tuple[int, int]:
    """``(pages_per_row, rows_per_page)`` of a scale pool."""
    per_page = group * block_size
    if LANES % block_size:
        raise ValueError(f"an int8 pool's block size must divide {LANES}, "
                         f"got {block_size}")
    if LANES % per_page == 0:
        return LANES // per_page, 1
    rows = 1
    while rows * LANES < per_page:
        rows *= 2
    if rows > SUBLANES:
        raise ValueError(f"a page's scales ({group} heads x {block_size}) "
                         f"overrun one ({SUBLANES}, {LANES}) block")
    return 1, rows


def value_shape(layers: int, pages: int, block_size: int, kv_heads: int,
                head_dim: int, group: int) -> Tuple[int, ...]:
    return (layers, kv_heads // group, pages, block_size,
            row_lanes(group, head_dim))


def scale_shape(layers: int, pages: int, block_size: int, kv_heads: int,
                group: int) -> Tuple[int, ...]:
    per_row, page_rows = scale_rows(block_size, group)
    rows = -(-pages // per_row) * page_rows
    return (layers, kv_heads // group, -(-rows // SUBLANES) * SUBLANES,
            LANES)


def values_of(pool):
    return pool.values if isinstance(pool, QuantPages) else pool


def pages_of(pool) -> int:
    """Physical pages of a pool, the trash page included."""
    return values_of(pool).shape[2]


def block_size_of(pool) -> int:
    return values_of(pool).shape[3]


class Geometry(NamedTuple):
    group: int            # G: KV heads in a row
    head_dim: int
    block_size: int
    lanes: int            # W: lanes of a value row
    pages_per_row: int    # pages in a scale row (1 for a float pool)
    rows_per_page: int    # scale rows of a page (1 for a float pool)


def geometry(values, scales, head_dim: int, kv_heads: int) -> Geometry:
    """The layout of a stored pool's ``values`` and (int8) ``scales``
    arrays, or their shapes, holding ``kv_heads`` KV heads as the arrays
    are seen (per device inside a ``shard_map``): the shapes alone cannot
    tell padding lanes from heads.  Raises when the arrays are not a pool
    of that layout."""
    vshape = getattr(values, "shape", values)
    _, Hg, _, bs, W = vshape
    G = kv_heads // Hg
    if G < 1 or G * Hg != kv_heads or row_lanes(G, head_dim) != W:
        raise ValueError(f"pool rows {vshape} do not hold {kv_heads} KV "
                         f"heads of {head_dim}")
    per_row = page_rows = 1
    if scales is not None:
        sshape = getattr(scales, "shape", scales)
        per_row, page_rows = scale_rows(bs, G)
        if tuple(sshape[:2]) != tuple(vshape[:2]) or sshape[3] != LANES \
                or sshape[2] % SUBLANES:
            raise ValueError(f"scale rows {sshape} do not fit values "
                             f"{vshape} with {G} heads a row")
    return Geometry(G, head_dim, bs, W, per_row, page_rows)


def _pool_geometry(pool, head_dim: int, kv_heads: int):
    if isinstance(pool, QuantPages):
        return geometry(pool.values, pool.scales, head_dim, kv_heads)
    return geometry(pool, None, head_dim, kv_heads)


def _scale_segments(scales, geo: Geometry):
    """Scale rows as ``(L, Hg, pages, G, bs)``, one page's segment a row
    (rows past the pool's last page included)."""
    L, Hg, rows, _ = scales.shape
    seg = geo.pages_per_row * geo.group * geo.block_size
    s = scales.reshape(L, Hg, rows // geo.rows_per_page,
                       geo.rows_per_page * LANES)
    return s[..., :seg].reshape(L, Hg, -1, geo.group, geo.block_size)


# ---------------------------------------------------------------------------
# from the natural (..., pages, block_size, Hkv, D) layout
# ---------------------------------------------------------------------------

def from_natural(natural, *, quantized: bool = False):
    """A pool in the stored layout, ``G = head_group(D, Hkv)``, from a
    natural ``(L, P, bs, Hkv, D)`` array (tests build pools this way);
    ``quantized`` packs it as int8 ``QuantPages``."""
    L, P, bs, Hkv, D = natural.shape
    G = head_group(D, Hkv)
    W = row_lanes(G, D)
    if quantized:
        natural, snat = quantize(natural)
    v = natural.reshape(L, P, bs, Hkv // G, G * D).transpose(0, 3, 1, 2, 4)
    v = jnp.pad(v, ((0, 0),) * 4 + ((0, W - G * D),))
    if not quantized:
        return v
    rows = scale_shape(L, P, bs, Hkv, G)[2]
    per_row, page_rows = scale_rows(bs, G)
    s = snat.reshape(L, P, bs, Hkv // G, G).transpose(0, 3, 1, 4, 2)
    n = -(-P // per_row)
    s = jnp.pad(s, ((0, 0), (0, 0), (0, n * per_row - P), (0, 0), (0, 0)))
    s = s.reshape(L, Hkv // G, n, per_row * G * bs)
    s = jnp.pad(s, ((0, 0), (0, 0), (0, 0),
                    (0, page_rows * LANES - per_row * G * bs)))
    s = s.reshape(L, Hkv // G, n * page_rows, LANES)
    return QuantPages(v, jnp.pad(s, ((0, 0), (0, 0),
                                     (0, rows - n * page_rows), (0, 0))))


# ---------------------------------------------------------------------------
# block-table gathers (reference path, dense-view fallback)
# ---------------------------------------------------------------------------

def gather(pool, block_tables, head_dim: int, kv_heads: int, *,
           layer=None, dtype=None):
    """Gather each table row's pages into contiguous tokens: ``(B, nblk *
    bs, kv_heads, D)`` of one ``layer``, or ``(L, B, nblk * bs, kv_heads,
    D)`` with ``layer=None``.  A ``QuantPages``
    pool gathers values and scales through the same table and
    dequantizes to ``dtype`` (f32 by default); a plain pool keeps its
    dtype.  Entries past a slot's length point at the trash page; callers
    mask them by length."""
    geo = _pool_geometry(pool, head_dim, kv_heads)
    G, bs = geo.group, geo.block_size
    B, nblk = block_tables.shape
    vals = values_of(pool)
    Hg = vals.shape[1]

    def pick(a):                         # page gather, layer first
        a = a if layer is None else a[layer][None]
        return a[:, :, block_tables]     # (L', Hg, B, nblk, ...)

    v = pick(vals)[..., :G * head_dim].reshape(-1, Hg, B, nblk, bs, G,
                                               head_dim)
    v = v.transpose(0, 2, 3, 4, 1, 5, 6).reshape(-1, B, nblk * bs, Hg * G,
                                                 head_dim)
    if isinstance(pool, QuantPages):
        s = pick(_scale_segments(pool.scales, geo))  # (L', Hg, B, nblk, G, bs)
        s = s.transpose(0, 2, 3, 5, 1, 4).reshape(-1, B, nblk * bs, Hg * G)
        v = dequantize(v, s, dtype or jnp.float32)
    return v if layer is None else v[0]


# ---------------------------------------------------------------------------
# in-place writes: token rows and whole pages
# ---------------------------------------------------------------------------

# Every scatter and gather below indexes each pool axis but the minor
# one (a whole W-lane value row; single f32 scales by row and lane), so XLA
# keeps the stored layout and updates the buffer in place: a window across
# head groups or layers would make it relayout the whole pool first.

def _layer_index(layer, n_layers: int, extra: int):
    """The layer index array: the given scalar, or every layer on a new
    leading axis ahead of ``extra`` broadcast axes."""
    if layer is not None:
        return layer
    return jnp.arange(n_layers).reshape((n_layers,) + (1,) * extra)


def _set_value_rows(values, rows, pages, offsets, layer, geo: Geometry):
    """rows ``(N, Hkv, D)`` (``layer`` given) or ``(L, N, Hkv, D)`` into
    ``values[layer, g, pages[n], offsets[n], :]``, padding lanes zero."""
    L, Hg = values.shape[:2]
    GD = geo.group * geo.head_dim
    upd = rows.reshape(*rows.shape[:-2], Hg, GD).astype(values.dtype)
    upd = jnp.pad(upd, ((0, 0),) * (upd.ndim - 1) + ((0, geo.lanes - GD),))
    lyr = _layer_index(layer, L, 2)
    g = jnp.arange(Hg)
    return values.at[lyr, g, pages[:, None], offsets[:, None]].set(
        upd, mode=_IN_BOUNDS)


def _scale_lanes(geo: Geometry, pages, offsets):
    """(row, lane) of each token's scale for each head of its group:
    ``(N, G)`` arrays."""
    G, bs = geo.group, geo.block_size
    first = (pages // geo.pages_per_row) * geo.rows_per_page
    flat = ((pages % geo.pages_per_row) * (G * bs) + offsets)[:, None] \
        + jnp.arange(G, dtype=offsets.dtype)[None] * bs
    return first[:, None] + flat // LANES, flat % LANES


def _set_scale_rows(scales, srows, pages, offsets, layer, geo: Geometry):
    """srows ``(N, Hkv)`` (``layer`` given) or ``(L, N, Hkv)`` into the
    token's lane of each head's page segment."""
    L, Hg = scales.shape[:2]
    row, lane = _scale_lanes(geo, pages, offsets)
    upd = srows.reshape(*srows.shape[:-1], Hg, geo.group)
    upd = jnp.swapaxes(upd, -1, -2)                     # (..., N, G, Hg)
    lyr = _layer_index(layer, L, 3)
    g = jnp.arange(Hg)
    return scales.at[lyr, g, row[..., None], lane[..., None]].set(
        upd, mode=_IN_BOUNDS)


def write_rows(pool, rows, pages, offsets, *, layer=None):
    """Write token rows into a stored pool in place: ``rows`` ``(N, Hkv,
    D)`` go to layer ``layer``, page ``pages[n]``, offset ``offsets[n]``;
    with ``layer=None`` they carry every layer, ``(L, N, Hkv, D)``.  A
    ``QuantPages`` pool quantizes the float rows on the way in (int8 rows
    to the values, their per-row f32 scales to the scale rows), so the
    pool only ever holds quantized blocks.  Several rows may name the
    trash page: which of them lands there is immaterial."""
    pages = jnp.asarray(pages, jnp.int32)
    offsets = jnp.asarray(offsets, jnp.int32)
    geo = _pool_geometry(pool, rows.shape[-1], rows.shape[-2])
    if not isinstance(pool, QuantPages):
        return _set_value_rows(pool, rows, pages, offsets, layer, geo)
    qv, qs = quantize(rows)
    return QuantPages(
        _set_value_rows(pool.values, qv, pages, offsets, layer, geo),
        _set_scale_rows(pool.scales, qs, pages, offsets, layer, geo))


def write_pages(pool, natural, pages):
    """Write whole pages of every layer: ``natural`` ``(L, n, bs, Hkv, D)``
    float blocks into physical pages ``pages`` (n,)."""
    L, n, bs, Hkv, D = natural.shape
    pages = jnp.asarray(pages, jnp.int32)
    tok_pages = jnp.repeat(pages, bs)
    offsets = jnp.tile(jnp.arange(bs, dtype=jnp.int32), n)
    return write_rows(pool, natural.reshape(L, n * bs, Hkv, D), tok_pages,
                      offsets)


def copy_pages(pool, src, dst, head_dim: int, kv_heads: int):
    """Copy physical pages ``src`` onto ``dst`` in every layer, values and
    scales bit for bit (copy-on-write)."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    v = values_of(pool)
    L, Hg = v.shape[:2]
    lyr = _layer_index(None, L, 2)
    g = jnp.arange(Hg)[:, None]
    v = v.at[lyr, g, dst].set(v[lyr, g, src], mode=_IN_BOUNDS)
    if not isinstance(pool, QuantPages):
        return v
    geo = _pool_geometry(pool, head_dim, kv_heads)
    s = pool.scales
    bs = geo.block_size
    offs = jnp.tile(jnp.arange(bs, dtype=jnp.int32), src.shape[0])
    r_src, l_src = _scale_lanes(geo, jnp.repeat(src, bs), offs)
    r_dst, l_dst = _scale_lanes(geo, jnp.repeat(dst, bs), offs)
    lyr = _layer_index(None, L, 3)
    g = jnp.arange(Hg)[:, None, None]
    return QuantPages(v, s.at[lyr, g, r_dst, l_dst].set(
        s[lyr, g, r_src, l_src], mode=_IN_BOUNDS))
