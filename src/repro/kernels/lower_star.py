"""Pallas TPU kernels for the lower-star discrete gradient.

TARGET: TPU v5e, compiled with Mosaic; on other platforms the same kernels
run in Pallas interpret mode (:func:`interpret_mode` decides, from the
platform alone).

Layout: vertices on lanes.  A block of vertices is an ``(8, lanes)``
int32 tile, and the 74 packed star rows stack on a leading axis,
``(74, 8, lanes)``.  The neighbor table is static, so the 222 neighbor
selections stack tile-shaped planes and never gather; the face table
enters as a constant input, and the unpaired-face counts are kept
incrementally, so no step gathers rows by a table either.  Priority-
queue pops are masked lexicographic argmins over the row axis (three
column passes of min — no int64 packed keys inside a kernel), and
scatter-style updates are selects.  Both kernels share this core
(:func:`_pair_planes`) and write their results as 19 int32 words per
vertex: 4 row bytes per word, the 74 row bytes followed by the vertex
byte (``vstat`` in the status words, ``vpart`` in the partner words).
On the pipeline's fused path :func:`fields_from_words` goes on, in the
same device program, from the words to the dense gradient fields in the
flat sid layout (static shifts and selects, no scatter), so only the
fields reach the host.  :func:`host_rows` (or, inside a device program,
:func:`_device_rows`) turns the words back into the (n, 74) int8 rows
for the host scatter, which stays the oracle.

1. **Fused kernel** (:func:`fused_lower_star_gradient_pallas`) — the
   production front-end.  The grid is (batch, z-plane, y-tile).  Each step
   reads the 3 z-planes x 3 y-tiles around its tile (plain blocked
   BlockSpecs; the z halo planes are part of the input volume, so the
   shardmap front's ring-exchanged ghost planes feed it directly), builds
   the 27 neighbor planes in VMEM with sublane/lane rolls masked at the
   grid boundary, then pairs 128 lanes at a time.  No (nv, 27) tensor
   ever touches HBM.

2. **Pre-pass kernel** (:func:`lower_star_gradient_pallas`) — the im2col
   path kept as a fallback and oracle cross-check: the stencil gather
   happens outside as an (n, 27) tensor.  Inputs are *bucket-padded* to
   power-of-two multiples of the tile so distinct lengths within one
   bucket share a compiled program (see :func:`bucket_len`; probe compile
   reuse via ``prepass_cache_size``).

Ranks enter the kernels as int32; the wrappers narrow int64 ranks given a
``rank_bound`` below 2**31 and refuse 64-bit keys they cannot narrow (the
rank-free streaming keys run on the XLA ``jax`` kernel instead).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import gradient as GR
from repro.core import grid as G

R = GR.NROWS                        # 74 packed star rows
EDGE_ROWS = G.NSTAR[1]              # rows [0, 14) are edges
TET_ROWS = GR.ROW_OFF[3]            # rows [50, 74) are tetrahedra
NOT_L, AVAIL, TAIL, HEAD, CRIT = (GR.NOT_L, GR.AVAIL, GR.TAIL, GR.HEAD,
                                  GR.CRIT)
# static star table: the neighbor indices of each row's other vertices
_OTH = tuple(tuple(int(o) for o in row if o >= 0)
             for row in GR.PACKED["others"])
WORDS = -(-(R + 1) // 4)            # 19 int32 words: 74 row bytes + 1
SUBLANES, LANES = 8, 128
_INF = int(np.iinfo(np.int32).max)
_I32 = np.int32                      # explicit: x64 is on process-wide


def interpret_mode() -> bool:
    """Pallas kernels compile with Mosaic on a TPU and are interpreted
    on every other platform."""
    return jax.default_backend() != "tpu"


def _as_int32(x, rank_bound):
    """int32 ranks for a kernel: int32 passes, 64-bit ranks narrow when
    ``rank_bound`` proves they fit, anything else is refused."""
    if x.dtype == jnp.int32:
        return x
    if rank_bound is not None and int(rank_bound) < 2 ** 31:
        return x.astype(jnp.int32)
    raise TypeError(
        f"Pallas lower-star kernels take int32 ranks; got {x.dtype} with "
        f"rank_bound={rank_bound}.  Pass a rank_bound < 2**31, or run "
        "rank-free 64-bit keys on the 'jax' kernel")


# --------------------------------------------------------------------------
# the shared pairing core: star rows stacked on the leading axis
# --------------------------------------------------------------------------

def fid_planes(shape):
    """The face table as (3, R) + shape int32 planes (-1 padded): the
    kernels' only constant input (kernels may not capture arrays)."""
    fid = np.asarray(GR.PACKED["fid"], np.int32).T             # (3, R)
    return jnp.broadcast_to(jnp.asarray(fid).reshape(fid.shape + (1,) * len(
        shape)), fid.shape + tuple(shape))


def _sort3_desc(a, b, c):
    a, b = jnp.maximum(a, b), jnp.minimum(a, b)
    a, c = jnp.maximum(a, c), jnp.minimum(a, c)
    b, c = jnp.maximum(b, c), jnp.minimum(b, c)
    return a, b, c


def _lexmin(mask, keys, rid):
    """(row, any) of the lexicographically smallest key row under
    ``mask`` (R, ...); the row is R where the mask is empty."""
    m = mask
    for kc in keys:
        v = jnp.where(m, kc, _I32(_INF))
        m = m & (v == v.min(axis=0, keepdims=True))
    idx = jnp.where(m, rid, _I32(R)).min(axis=0)
    return idx, idx < R


def _pair_planes(nb, ov, fid):
    """Branchless ProcessLowerStars over one tile of vertices.

    nb: 27 int32 neighbor-order planes (-1 outside the grid), ov: the
    vertex-order plane, fid: :func:`fid_planes` of the tile shape.
    Returns (status, partner) as (R,) + tile int32 and (vstat, vpart)
    planes — the same values as ``ref.lower_star_gradient_jnp``.

    The unpaired-face counts are kept incrementally: every in-star row
    starts with all of its faces available (0 / 2 / 3 for edges /
    triangles / tets), and each row leaving AVAIL decrements the count
    of the rows it is a face of, so no step gathers rows by a table."""
    neg = jnp.full(ov.shape, -1, jnp.int32)
    vals = [jnp.stack([nb[o[m]] if m < len(o) else neg for o in _OTH])
            for m in range(3)]                                  # 3 x (R,..)
    rid = jax.lax.broadcasted_iota(jnp.int32, vals[0].shape, 0)
    lower = [(v >= 0) & (v < ov) for v in vals]
    in_l = lower[0] & (lower[1] | (rid < EDGE_ROWS)) \
        & (lower[2] | (rid < TET_ROWS))
    keys = _sort3_desc(*vals)
    status = jnp.where(in_l, _I32(AVAIL), _I32(NOT_L))
    partner = jnp.full(status.shape, -1, jnp.int32)
    nuf = jnp.where(rid < EDGE_ROWS, _I32(0),
                    jnp.where(rid < TET_ROWS, _I32(2), _I32(3)))

    def cofaces_of(row):
        """(R, ...) 1 where ``row`` (one per vertex) is a face of r."""
        return ((fid[0] == row) | (fid[1] == row) | (fid[2] == row)
                ).astype(jnp.int32)

    delta, has_edge = _lexmin(in_l & (rid < EDGE_ROWS), keys, rid)
    vstat = jnp.where(has_edge, _I32(TAIL), _I32(CRIT))
    vpart = jnp.where(has_edge, delta, _I32(-1))
    hit = rid == delta
    status = jnp.where(hit, _I32(HEAD), status)
    partner = jnp.where(hit, _I32(-2), partner)
    nuf = nuf - cofaces_of(delta)

    def body(carry):
        status, partner, nuf, _ = carry
        avail = status == AVAIL
        alpha, any1 = _lexmin(avail & (nuf == 1), keys, rid)
        gamma, any0 = _lexmin(avail & (nuf == 0), keys, rid)
        # alpha's one available face: its first face row still AVAIL
        is_a = rid == alpha
        f = [jnp.where(is_a, fid[m], _I32(-1)).max(axis=0) for m in range(3)]
        ok = [jnp.where(avail & (rid == f_m), _I32(1), _I32(0)).max(axis=0)
              > 0 for f_m in f]
        face = jnp.where(ok[0], f[0], jnp.where(ok[1], f[1],
                                                jnp.where(ok[2], f[2], f[0])))
        do0 = ~any1 & any0
        is_a = is_a & any1
        is_f = (rid == face) & any1
        is_g = (rid == gamma) & do0
        status = jnp.where(is_a, _I32(HEAD), status)
        status = jnp.where(is_f, _I32(TAIL), status)
        status = jnp.where(is_g, _I32(CRIT), status)
        partner = jnp.where(is_a, face, partner)
        partner = jnp.where(is_f, alpha, partner)
        nuf = nuf - jnp.where(any1, cofaces_of(alpha) + cofaces_of(face),
                              jnp.where(do0, cofaces_of(gamma), _I32(0)))
        go = jnp.max((any1 | any0).astype(jnp.int32)) > 0
        return status, partner, nuf, go

    status, partner, _, _ = jax.lax.while_loop(
        lambda c: c[3], body, (status, partner, nuf, jnp.asarray(True)))
    return status, partner, vstat, vpart


def _pack(planes):
    """Row-byte planes (values in int8 range) -> WORDS int32 planes."""
    planes = planes + [jnp.zeros_like(planes[0])] * (4 * WORDS - len(planes))
    return [(planes[4 * q] & 0xFF) | ((planes[4 * q + 1] & 0xFF) << 8)
            | ((planes[4 * q + 2] & 0xFF) << 16) | (planes[4 * q + 3] << 24)
            for q in range(WORDS)]


def _store_rows(st_ref, pt_ref, at, status, partner, vstat, vpart):
    for ref, rows, v in ((st_ref, status, vstat), (pt_ref, partner, vpart)):
        for q, w in enumerate(_pack([rows[r] for r in range(R)] + [v])):
            ref[at(q)] = w


def _split_bytes(words, axis: int):
    """int32 words (word axis at ``axis``) -> their int8 row bytes along
    that axis, in little-endian order, cut to the R + 1 used bytes."""
    shift = jnp.asarray([24, 16, 8, 0], jnp.int32).reshape(
        (4,) + (1,) * (words.ndim - axis - 1))
    b = ((jnp.expand_dims(words, axis + 1) << shift) >> 24).astype(jnp.int8)
    b = b.reshape(words.shape[:axis] + (4 * WORDS,) + words.shape[axis + 1:])
    return jax.lax.slice_in_dim(b, 0, R + 1, axis=axis)


def _split_rows(st, pt, xp=jnp):
    """(n, R + 1) status/partner bytes -> (status, partner, vstat, vpart)."""
    return st[:, :R], pt[:, :R], st[:, R], pt[:, R].astype(xp.int32)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


# --------------------------------------------------------------------------
# bucket padding — compile once per (bucket, dtype), not once per length
# --------------------------------------------------------------------------

def bucket_len(n: int, tile: int) -> int:
    """Smallest power-of-two multiple of ``tile`` >= n.

    Distinct input lengths that land in one bucket share a compiled
    program; the padding waste is < 2x and the padded lanes retire after
    the first loop iteration (everything is NOT_L for an order of -1)."""
    b = tile
    while b < n:
        b *= 2
    return b


# --------------------------------------------------------------------------
# pre-pass (im2col) kernel — fallback + oracle cross-check
# --------------------------------------------------------------------------

def _prepass_kernel(x_ref, fid_ref, st_ref, pt_ref):
    planes = [x_ref[k] for k in range(28)]      # 27 neighbors + the vertex
    rows = _pair_planes(planes[:27], planes[27], fid_ref[...])
    _store_rows(st_ref, pt_ref, lambda q: q, *rows)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _prepass_call(nbrs, ov, *, tile: int, interpret: bool):
    """nbrs (npad, 27), ov (npad,) int32, npad a multiple of ``tile``.

    A block of ``tile`` vertices is laid out as an (8, tile // 8) tile."""
    npad = nbrs.shape[0]
    nblk, t8 = npad // tile, tile // SUBLANES
    x = jnp.concatenate([nbrs, ov[:, None]], axis=1).T          # (28, npad)
    x = x.reshape(28, nblk, SUBLANES, t8).transpose(0, 2, 1, 3) \
        .reshape(28, SUBLANES, nblk * t8)
    out = pl.BlockSpec((WORDS, SUBLANES, t8), lambda i: (_I32(0), _I32(0), i))
    shape = jax.ShapeDtypeStruct((WORDS, SUBLANES, nblk * t8), jnp.int32)
    st, pt = pl.pallas_call(
        _prepass_kernel,
        grid=(nblk,),
        in_specs=[pl.BlockSpec((28, SUBLANES, t8),
                               lambda i: (_I32(0), _I32(0), i)),
                  pl.BlockSpec((3, R, SUBLANES, t8),
                               lambda i: (_I32(0),) * 4)],
        out_specs=[out, out],
        out_shape=[shape, shape],
        interpret=interpret,
    )(x, fid_planes((SUBLANES, t8)))

    def vertex_major(w):            # bytes split in the tile layout
        return _split_bytes(w, 0).reshape(R + 1, SUBLANES, nblk, t8) \
            .transpose(2, 1, 3, 0).reshape(npad, R + 1)
    return _split_rows(vertex_major(st), vertex_major(pt))


def prepass_cache_size() -> int:
    """Number of compiled pre-pass programs (the bucket-reuse probe)."""
    return _prepass_call._cache_size()


def lower_star_gradient_pallas(nbrs, ov, tile: int = SUBLANES * LANES,
                               rank_bound: int | None = None):
    """Pallas-tiled lower-star gradient over a pre-gathered im2col tensor.

    nbrs (n, 27), ov (n,).  The vertex axis is bucket-padded to a
    power-of-two multiple of ``tile`` (a multiple of 8; of 1024 on a TPU)
    so nearby lengths reuse one compiled program.  ``rank_bound``
    (static, = grid.nv) lets 64-bit ranks narrow to int32.
    """
    n = nbrs.shape[0]
    npad = bucket_len(n, tile)
    nbrs = _as_int32(jnp.asarray(nbrs), rank_bound)
    ov = _as_int32(jnp.asarray(ov), rank_bound)
    nbrs_p = jnp.pad(nbrs, ((0, npad - n), (0, 0)), constant_values=-1)
    ov_p = jnp.pad(ov, (0, npad - n), constant_values=-1)
    status, partner, vstat, vpart = _prepass_call(
        nbrs_p, ov_p, tile=tile, interpret=interpret_mode())
    return status[:n], partner[:n], vstat[:n], vpart[:n]


# --------------------------------------------------------------------------
# fused kernel — gather + pairing in one pass over the volume
# --------------------------------------------------------------------------

def _fused_kernel(*refs, ny: int, nx: int):
    tiles = [ref[0, 0] for ref in refs[:9]]     # (z+dz, y-tile j+dy)
    fid_ref, st_ref, pt_ref, nb_ref = refs[9:]
    ty, nxp = tiles[0].shape
    row = jax.lax.broadcasted_iota(jnp.int32, (ty, nxp), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (ty, nxp), 1)
    gy = pl.program_id(2) * _I32(ty) + row
    neg = _I32(-1)

    def roll(x, shift, axis):
        return pltpu.roll(x, _I32(shift % x.shape[axis]), axis)

    # the 27 neighbor planes (dx fastest, as _nbr_index), -1 outside
    for dz in range(3):
        prev, cur, nxt = tiles[3 * dz: 3 * dz + 3]
        ys = [jnp.where(row == 0, roll(prev, 1, 0), roll(cur, 1, 0)),
              cur,
              jnp.where(row == ty - 1, roll(nxt, -1, 0), roll(cur, -1, 0))]
        ys[0] = jnp.where(gy == 0, neg, ys[0])
        ys[2] = jnp.where(gy == ny - 1, neg, ys[2])
        for dy, p in enumerate(ys):
            nb_ref[9 * dz + 3 * dy] = jnp.where(lane == 0, neg, roll(p, 1, 1))
            nb_ref[9 * dz + 3 * dy + 1] = p
            nb_ref[9 * dz + 3 * dy + 2] = jnp.where(lane == nx - 1, neg,
                                                    roll(p, -1, 1))

    def chunk(c):
        lanes = pl.ds(pl.multiple_of(c * _I32(LANES), LANES), LANES)
        nb = [nb_ref[k, :, lanes] for k in range(27)]
        rows = _pair_planes(nb, nb[13], fid_ref[...])
        _store_rows(st_ref, pt_ref, lambda q: (0, 0, q, slice(None), lanes),
                    *rows)
        return c + 1

    # an explicit int32 counter: fori_loop's would be int64 under x64
    jax.lax.while_loop(lambda c: c < nxp // LANES, chunk, _I32(0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fused_call(vol, *, interpret: bool):
    """vol: (B, nz+2, ny, nx) int32 orders whose first/last z-planes are
    halo planes (-1 at the global boundary).  Returns the (status,
    partner) words of the B*nz*ny*nx owned vertices, each (B, nz, 19,
    ny8, nx128) int32 with y/x padded to the (8, 128) tile."""
    B, nzh, ny, nx = vol.shape
    nz = nzh - 2
    nyp, nxp = _round_up(ny, SUBLANES), _round_up(nx, LANES)
    nyt = nyp // SUBLANES
    vol = jnp.pad(vol, ((0, 0), (0, 0), (0, nyp - ny), (0, nxp - nx)),
                  constant_values=-1)

    def tile(dz, dy):
        return pl.BlockSpec(
            (1, 1, SUBLANES, nxp),
            lambda b, z, j: (b, z + dz, jnp.minimum(
                jnp.maximum(j + dy, _I32(0)), _I32(nyt - 1)), _I32(0)))

    out = pl.BlockSpec((1, 1, WORDS, SUBLANES, nxp),
                       lambda b, z, j: (b, z, _I32(0), j, _I32(0)))
    shape = jax.ShapeDtypeStruct((B, nz, WORDS, nyp, nxp), jnp.int32)
    return pl.pallas_call(
        functools.partial(_fused_kernel, ny=ny, nx=nx),
        grid=(B, nz, nyt),
        in_specs=[tile(dz, dy) for dz in range(3) for dy in (-1, 0, 1)]
        + [pl.BlockSpec((3, R, SUBLANES, LANES),
                        lambda b, z, j: (_I32(0),) * 4)],
        out_specs=[out, out],
        out_shape=[shape, shape],
        scratch_shapes=[pltpu.VMEM((27, SUBLANES, nxp), jnp.int32)],
        interpret=interpret,
    )(*[vol] * 9, fid_planes((SUBLANES, LANES)))


def _device_rows(words, ny: int, nx: int):
    """Packed rows from :func:`_fused_call` words, unpacked on the device
    (for callers that keep the rows in a device program).  The bytes are
    split off in the kernel's (…, y, x) layout and only int8 rows move
    vertex-major: a (…, 19) int32 minor axis would pad to 128 lanes on a
    TPU."""
    st, pt = (jnp.moveaxis(_split_bytes(w[..., :ny, :nx], 2), 2, -1)
              .reshape(-1, R + 1) for w in words)
    return _split_rows(st, pt)


def device_fields_fit(grid) -> bool:
    """Whether :func:`fields_from_words` can build ``grid``'s fields: every
    sid space below 2**31, so every pair array is int32."""
    return max(grid.sid_space(k) for k in range(grid.dim + 1)) < 2 ** 31


def _byte(w, r: int):
    """Row byte ``r`` of (…, WORDS, y, x) words as a sign-extended int32
    (…, y, x) plane (the shifts of :func:`_split_bytes`)."""
    return (w[:, :, r // 4] << _I32(24 - 8 * (r % 4))) >> _I32(24)


def _shift(plane, c):
    """``plane[b, z + cz, y + cy, x + cx]`` for a (B, z, y, x) plane and
    c in {0, 1}^3: a static slice and pad, 0 (NOT_L) beyond the grid."""
    cx, cy, cz = (int(a) for a in c)
    cut = plane[:, cz:, cy:, cx:]
    return jnp.pad(cut, ((0, 0), (0, cz), (0, cy), (0, cx)))


def _lookup(idx, table):
    """``table[idx]`` for a tiny static table, as a select chain (0 where
    idx is out of range): no gather."""
    out = jnp.zeros(idx.shape, jnp.int32)
    for q, v in enumerate(table):
        out = jnp.where(idx == q, _I32(int(v)), out)
    return out


def _interleave(planes):
    """T same-shape int32 or bool planes -> (rows, 128 * T) whose flat
    order holds plane t's element i at ``i * T + t`` (the flat sid layout
    ``sid = vid * T + t``), the tail past ``T * planes[0].size`` padding.

    The interleave is a lane permutation, done on the MXU: 128 elements
    of each plane side by side, times a 0/1 matrix.  Each output takes
    exactly one product, so it is exact on bytes in bfloat16 with float32
    sums; an int32 plane goes through as its 4 bytes (of value + 1, so
    -1 reads 0).  A minor axis of T would pad to 128 lanes."""
    T, n = len(planes), planes[0].size
    pad = _round_up(n, LANES) - n
    x = jnp.concatenate([jnp.pad(p.reshape(-1), (0, pad)).reshape(-1, LANES)
                         for p in planes], axis=1)    # lane t * 128 + i
    src = jax.lax.broadcasted_iota(jnp.int32, (T * LANES,) * 2, 0)
    dst = jax.lax.broadcasted_iota(jnp.int32, (T * LANES,) * 2, 1)
    perm = (dst == (src % LANES) * T + src // LANES).astype(jnp.bfloat16)

    def permute(v):
        return jnp.dot(v.astype(jnp.bfloat16), perm,
                       preferred_element_type=jnp.float32).astype(jnp.int32)

    if x.dtype == jnp.bool_:
        return permute(x) > 0
    u = x + _I32(1)
    out = sum(permute((u >> _I32(8 * b)) & _I32(0xFF)) << _I32(8 * b)
              for b in range(4))
    return out - _I32(1)


def fields_from_words(words, grid, *, z0=None, ghost=None):
    """The dense gradient fields of a batch, built on the device from
    :func:`_fused_call` words: the device twin of
    ``core.gradient.scatter_results_batch`` over the same rows.

    A k-simplex ``(base, t)`` is written by exactly one row: the row
    ``ROW_OFF[k] + t * (k + 1) + j`` of its order-maximal vertex
    ``base + c_j`` (``c_j = STAR[k][t * (k + 1) + j, 1:]`` in {0, 1}^3), and
    at its other vertices that row is NOT_L.  So every output is a select
    over the k + 1 row planes shifted by a static ``c_j``: no scatter, and
    no gather over the vertex axis.  The words are cropped to the grid
    before any shift (a padded lane has order -1 and reads CRIT), and
    reads beyond the grid are NOT_L, which gives -1 / False.

    A z-slab of ``grid`` (the shardmap front-end) passes its words, the
    global plane index ``z0`` of its first plane (traced) and ``ghost``:
    the words of the next slab's first plane, all NOT_L (0) above the
    grid.  The fields then cover the sids whose base vertex lies in the
    slab, numbered globally; the rows of a simplex whose top vertex lies
    one plane up come from the ghost plane.

    Returns ``(pair_up, pair_down, crit, n_critical)``: dicts of int32
    pair and bool flag arrays (:func:`device_fields_fit` must hold) whose
    first ``B * sid_space(k)`` elements (a slab: ``B * nz_slab * ny * nx
    * T_k``), in C order, are the B fields' arrays in the host's flat sid
    layout, back to back; and the (B,) int32 count of critical simplices
    of each field.
    """
    nx, ny, _ = grid.dims
    st, pt = (w[..., :ny, :nx] for w in words)
    nz = st.shape[1]
    d = grid.dim
    off = GR.row_sid_offsets(grid)
    vid = (jax.lax.broadcasted_iota(jnp.int32, (nz, ny, nx), 2)
           + _I32(nx) * (jax.lax.broadcasted_iota(jnp.int32, (nz, ny, nx), 1)
                         + _I32(ny) * jax.lax.broadcasted_iota(
                             jnp.int32, (nz, ny, nx), 0)))
    if z0 is not None:
        vid = vid + jnp.asarray(z0, jnp.int32) * _I32(nx * ny)
    if ghost is None:
        up_st, up_pt = st, pt
    else:
        up_st, up_pt = (jnp.concatenate([w, g[..., :ny, :nx]], axis=1)
                        for w, g in zip((st, pt), ghost))

    def row_plane(w, r, c):
        """Row byte ``r`` read at shift ``c``, over the slab's planes."""
        p = _shift(_byte(w, r), c)
        return p if ghost is None else p[:, :nz]

    def lin(c):
        return int(c[0]) + nx * (int(c[1]) + ny * int(c[2]))

    vs, vp = _byte(st, R), _byte(pt, R)
    crit = {0: vs == CRIT}
    pair_up, pair_down = {}, {}
    if d >= 1:
        pair_up[0] = jnp.where(vs == TAIL, vid * _I32(G.NTYPES[1])
                               + _lookup(vp, off[1]), _I32(-1))
    n_crit = crit[0].sum(axis=(1, 2, 3), dtype=jnp.int32)

    def paired(sel, s, p, c, role):
        """Fold row plane (s, p), read at shift c, into the selection of
        the rows whose status is ``role``: (any, partner, lin(c))."""
        hit = s == role
        has, part, sh = sel
        return (has | hit, jnp.where(hit, p, part),
                jnp.where(hit, _I32(lin(c)), sh))

    zero, none = jnp.zeros(vs.shape, jnp.int32), jnp.zeros(vs.shape, bool)
    for k in range(1, d + 1):
        T = G.NTYPES[k]
        ups, downs, crits = [], [], []
        for t in range(T):
            head, tail, is_crit = (none, zero, zero), (none, zero, zero), none
            for j in range(k + 1):
                rl = t * (k + 1) + j
                c = G.STAR[k][rl, 1:]
                s = row_plane(up_st, GR.ROW_OFF[k] + rl, c)
                p = row_plane(up_pt, GR.ROW_OFF[k] + rl, c)
                is_crit = is_crit | (s == CRIT)
                head = paired(head, s, p, c, HEAD)
                if k < d:
                    tail = paired(tail, s, p, c, TAIL)
            has, p, sh = head
            v = vid + sh                        # the row's vertex
            if k == 1:      # an edge's head pairs with its vertex
                downs.append(jnp.where(has, v, _I32(-1)))
            else:
                downs.append(jnp.where(
                    has, v * _I32(G.NTYPES[k - 1])
                    + _lookup(p - _I32(GR.ROW_OFF[k - 1]), off[k - 1]),
                    _I32(-1)))
            if k < d:
                has, p, sh = tail
                ups.append(jnp.where(
                    has, (vid + sh) * _I32(G.NTYPES[k + 1])
                    + _lookup(p - _I32(GR.ROW_OFF[k + 1]), off[k + 1]),
                    _I32(-1)))
            crits.append(is_crit)
            n_crit = n_crit + is_crit.sum(axis=(1, 2, 3), dtype=jnp.int32)
        pair_down[k] = _interleave(downs)
        crit[k] = _interleave(crits)
        if k < d:
            pair_up[k] = _interleave(ups)
    return pair_up, pair_down, crit, n_crit


def host_rows(words, ny: int, nx: int):
    """Packed rows from :func:`_fused_call` words, unpacked on the host.

    The device keeps the words in the kernel's (…, 19, y, x) layout (x on
    lanes: no padding); the host crops, moves the word axis last and
    reads each int32 word as its 4 little-endian row bytes."""
    out = []
    for w in words:
        w = np.asarray(w)[..., :ny, :nx]
        b = np.ascontiguousarray(np.moveaxis(w, 2, -1), dtype="<i4")
        out.append(b.view(np.int8).reshape(-1, 4 * WORDS))
    return _split_rows(*out, xp=np)


def fused_cache_size() -> int:
    """Number of compiled fused programs (recompile regression probe)."""
    return _fused_call._cache_size()


def fused_words(grid, orders, *, rank_bound: int | None = None):
    """Device half of :func:`fused_lower_star_gradient_pallas`: the
    kernel's packed words for (nv,) or (B, nv) rank fields."""
    nx, ny, nz = grid.dims
    o = jnp.asarray(orders).reshape(-1, nz, ny, nx)
    o = _as_int32(o, grid.nv if rank_bound is None else rank_bound)
    vol = jnp.pad(o, ((0, 0), (1, 1), (0, 0), (0, 0)), constant_values=-1)
    return _fused_call(vol, interpret=interpret_mode())


def fused_lower_star_gradient_pallas(grid, orders, *,
                                     rank_bound: int | None = None):
    """Fused gather+pairing over a whole grid (optionally a batch of them).

    grid: :class:`repro.core.grid.Grid`; orders: (nv,) or (B, nv) rank
    fields in vid layout.  Returns packed rows over the flattened batch
    (status (B*nv, 74) int8, partner int8, vstat (B*nv,) int8, vpart
    int32) as host arrays — no (nv, 27) tensor ever touches HBM.
    """
    nx, ny, _ = grid.dims
    return host_rows(fused_words(grid, orders, rank_bound=rank_bound), ny, nx)


def halo_words(ext, *, rank_bound: int | None = None):
    """Fused kernel words over z-slabs whose halo planes were exchanged
    already.

    ext: (B, nz_local+2, ny, nx) rank volumes; the first/last z-planes are
    the ghost planes received from the ring neighbors (-1 at the global
    boundary) — exactly the z halo the fused kernel reads, so the
    shardmap front-end feeds the kernel directly.  Returns the (status,
    partner) words of the owned vertices, as :func:`_fused_call`."""
    return _fused_call(_as_int32(jnp.asarray(ext), rank_bound),
                       interpret=interpret_mode())


def fused_rows_from_halo_volume(ext, *, rank_bound: int | None = None):
    """Packed rows of :func:`halo_words` over one (nz_local+2, ny, nx)
    halo-extended slab, for the nz_local*ny*nx owned vertices."""
    _, ny, nx = ext.shape
    return _device_rows(halo_words(jnp.asarray(ext)[None],
                                   rank_bound=rank_bound), ny, nx)
