"""The register-transfer semantics of a weight-stationary systolic array,
as the TPU v1's matrix unit (Jouppi et al., ISCA 2017), in plain numpy,
and the schedule that streams weight and activation tiles through it.
Imports nothing of the program.

The array has ``R`` rows and ``C`` columns of cells.  In every cycle each
register loads at the clock edge that ends the cycle:

* ``x[i][j]   <- x_in[i]`` (column 0) or ``x[i][j-1]``: activations shift
  right;
* ``tok[i][j] <- wswap[i]`` (column 0) or ``tok[i][j-1]``: a one-bit swap
  token travels beside them;
* ``wsh[i][j] <- w_in[j]`` (row 0) or ``wsh[i-1][j]`` while
  ``wshift[j]``, else holds: the shadow weights shift down;
* ``w[i][j]   <- wsh[i][j]`` while ``tok[i][j]``, else holds: the swap;
* ``p[i][j]   <- (0 if i == 0 else p[i-1][j]) + x[i][j] * w[i][j]``,
  wrapped to ``psum_bits`` bits: the partial sums pass down.

Every register starts at 0.  The outputs of a cycle are the bottom row's
partial sums, ``y[j] = p[R-1][j]``, before the clock edge.

The schedule (:func:`streams`): tile ``k`` of ``n`` is a weight matrix
``W_k [R, C]`` and ``M`` rows of activations ``X_k [M, R]`` (``M >=
2R - 1``), giving ``Y_k = X_k @ W_k``.  Tile ``k`` streams from cycle
``s_k = R + k M``: row ``r`` enters array row ``i`` at cycle ``s_k + r +
i`` (skewed, as the systolic schedule requires).  Its weights shift into
column ``j``'s shadow registers in the ``R`` cycles before ``s_k + j``,
bottom row first, while tile ``k - 1`` still streams; the swap token
enters row ``i`` at ``s_k + i - 1``, one step ahead of the tile's first
activation.  ``Y_k[r, j]`` is ``y[j]`` in cycle ``s_k + r + R + j + 1``;
the last appears in cycle ``R + n M + R + C - 1``, so a call runs
:func:`n_cycles` cycles.
"""
from __future__ import annotations

import numpy as np


def n_cycles(R: int, C: int, n_tiles: int, M: int) -> int:
    """Cycles that load, stream and drain ``n_tiles`` tiles."""
    return R + n_tiles * M + R + C


def tile_start(R: int, M: int, k: int) -> int:
    return R + k * M


def x_cycle(R: int, M: int, k: int, r: int, i: int) -> int:
    """The cycle row ``r`` of tile ``k`` enters array row ``i``."""
    return tile_start(R, M, k) + r + i


def shift_cycle(R: int, M: int, k: int, j: int, m: int) -> int:
    """The ``m``-th shift of tile ``k``'s weights into column ``j``; it
    carries ``W_k[R - 1 - m, j]``."""
    return tile_start(R, M, k) - R + j + m


def swap_cycle(R: int, M: int, k: int, i: int) -> int:
    """The cycle tile ``k``'s swap token enters row ``i``."""
    return tile_start(R, M, k) + i - 1


def out_cycle(R: int, M: int, k: int, r: int, j: int) -> int:
    """The cycle ``Y_k[r, j]`` is the output of column ``j``."""
    return tile_start(R, M, k) + r + R + j + 1


def streams(W: np.ndarray, X: np.ndarray) -> dict:
    """The input streams of ``n`` tiles: ``W [n, B, R, C]`` and ``X [n,
    B, M, R]`` int8, for ``B`` independent arrays.  Returns ``x [T, B,
    R]``, ``w [T, B, C]`` (int8), ``wshift [T, C]`` and ``wswap [T, R]``
    (bool)."""
    n, B, R, C = W.shape
    M = X.shape[2]
    if M < 2 * R - 1:
        raise ValueError(f"{M} rows a tile cannot hide a {R}-cycle reload")
    T = n_cycles(R, C, n, M)
    x = np.zeros((T, B, R), dtype=np.int8)
    w = np.zeros((T, B, C), dtype=np.int8)
    wshift = np.zeros((T, C), dtype=bool)
    wswap = np.zeros((T, R), dtype=bool)
    for k in range(n):
        for i in range(R):
            c = x_cycle(R, M, k, 0, i)
            x[c:c + M, :, i] = X[k, :, :, i].T
            wswap[swap_cycle(R, M, k, i), i] = True
        for j in range(C):
            c = shift_cycle(R, M, k, j, 0)
            w[c:c + R, :, j] = W[k, :, ::-1, j].T
            wshift[c:c + R, j] = True
    return {"x": x, "w": w, "wshift": wshift, "wswap": wswap}


def simulate(st: dict, psum_bits: int = 32) -> np.ndarray:
    """Step the array over the streams ``st`` (:func:`streams`); returns
    the outputs ``y [T, B, C]`` of every cycle as ``uint32`` (the
    ``psum_bits``-bit partial sums, two's complement)."""
    T, B, R = st["x"].shape
    C = st["w"].shape[2]
    mask = np.uint32((1 << psum_bits) - 1)
    x = np.zeros((B, R, C), dtype=np.int8)
    tok = np.zeros((R, C), dtype=bool)
    wsh = np.zeros((B, R, C), dtype=np.int8)
    w = np.zeros((B, R, C), dtype=np.int8)
    p = np.zeros((B, R, C), dtype=np.uint32)
    y = np.zeros((T, B, C), dtype=np.uint32)
    for t in range(T):
        y[t] = p[:, R - 1, :]
        prod = (x.astype(np.int32) * w.astype(np.int32)).astype(np.uint32)
        p_next = np.empty_like(p)
        p_next[:, 0, :] = prod[:, 0, :]
        p_next[:, 1:, :] = p[:, :-1, :] + prod[:, 1:, :]
        p = p_next & mask
        w = np.where(tok[None], wsh, w)
        above = np.concatenate([st["w"][t][:, None, :], wsh[:, :-1, :]],
                               axis=1)
        wsh = np.where(st["wshift"][t][None, None, :], above, wsh)
        tok = np.concatenate([st["wswap"][t][:, None], tok[:, :-1]], axis=1)
        x = np.concatenate([st["x"][t][:, :, None], x[:, :, :-1]], axis=2)
    return y


def sign_extend(y: np.ndarray, bits: int) -> np.ndarray:
    """``bits``-bit two's-complement values held in ``uint32`` (as
    :func:`simulate` returns them) -> the same values as 32-bit words."""
    half = np.uint32(1 << (bits - 1))
    return (np.asarray(y, dtype=np.uint32) ^ half) - half


def products(W: np.ndarray, X: np.ndarray, psum_bits: int = 32
             ) -> np.ndarray:
    """``Y_k = X_k @ W_k`` wrapped to ``psum_bits`` bits, ``[n, B, M,
    C]`` uint32: what the array computes."""
    Y = np.einsum("nbmr,nbrc->nbmc", X.astype(np.int64), W.astype(np.int64))
    return (Y & ((1 << psum_bits) - 1)).astype(np.uint32)


# ---------------------------------------------------------------------------
# packed lanes: bit b of 32 arrays' values in one uint32 word
# ---------------------------------------------------------------------------


def pack_bits(values: np.ndarray, bits: int) -> np.ndarray:
    """``values [..., B]`` (B a multiple of 32) -> ``[..., bits, B/32]``
    uint32 lane words: bit ``v % 32`` of word ``v // 32`` of row ``b`` is
    bit ``b`` of array ``v``'s value (two's complement, ``bits <= 32``)."""
    v = np.ascontiguousarray(np.asarray(values).astype("<u4"))
    planes = np.unpackbits(v[..., None].view(np.uint8), axis=-1,
                           bitorder="little")[..., :bits]
    packed = np.packbits(np.swapaxes(planes, -1, -2), axis=-1,
                         bitorder="little")
    return np.ascontiguousarray(packed).view("<u4")


def unpack_int8(words: np.ndarray) -> np.ndarray:
    """``[..., 8, N]`` lane words of 8-bit two's-complement values ->
    ``[..., 32 N]`` int8, the inverse of :func:`pack_bits`."""
    w = np.ascontiguousarray(np.asarray(words, dtype="<u4"))
    bits = np.unpackbits(w.view(np.uint8), axis=-1, bitorder="little")
    weights = (1 << np.arange(8, dtype=np.int32))
    weights[7] = -128
    return np.tensordot(bits.astype(np.int32), weights,
                        axes=([-2], [0])).astype(np.int8)
