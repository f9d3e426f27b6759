"""Key handling for FB+-tree: order-preserving byte encodings and key sets.

Keys are arbitrary byte strings. Device-side they live in a fixed-width,
zero-padded ``uint8[N, max_key_len]`` array plus ``int32[N]`` lengths. Order is
lexicographic over bytes with a length tie-break, which equals true
bytes-order as long as comparisons fall back to length when the padded bytes
are identical (a zero-padded key compares equal to its own prefix key).

The paper's §3.6 trick (add 128 to signed bytes so unsigned SIMD compares
work) appears here as the sign-bit flip in :func:`encode_int64`: signed
integers become order-preserving unsigned byte strings, after which all
comparisons in the tree are plain unsigned byte compares.
"""
from __future__ import annotations

from typing import Iterable, NamedTuple, Sequence, Union

import numpy as np

__all__ = [
    "KeySet",
    "encode_uint64",
    "encode_int64",
    "decode_uint64",
    "make_keyset",
    "pack_words",
    "lex_sort_indices",
    "lex_sort_indices_j",
    "compare_padded",
    "fnv1a_tags",
]


def encode_uint64(x: Union[int, np.ndarray]) -> np.ndarray:
    """uint64 -> big-endian 8 bytes (order-preserving)."""
    x = np.asarray(x, dtype=np.uint64)
    out = np.empty(x.shape + (8,), dtype=np.uint8)
    for i in range(8):
        out[..., i] = ((x >> np.uint64(8 * (7 - i))) & np.uint64(0xFF)).astype(np.uint8)
    return out


def encode_int64(x: Union[int, np.ndarray]) -> np.ndarray:
    """int64 -> order-preserving 8 bytes via sign-bit flip (paper §3.6)."""
    x = np.atleast_1d(np.asarray(x, dtype=np.int64))
    flipped = x.view(np.uint64) ^ np.uint64(1 << 63)
    return encode_uint64(flipped)


def decode_uint64(b: np.ndarray) -> np.ndarray:
    b = np.asarray(b, dtype=np.uint64)
    acc = np.zeros(b.shape[:-1], dtype=np.uint64)
    for i in range(8):
        acc = (acc << np.uint64(8)) | b[..., i]
    return acc


class KeySet(NamedTuple):
    """Fixed-width padded key batch."""

    bytes: np.ndarray  # uint8 [N, L] zero padded
    lens: np.ndarray   # int32 [N]

    @property
    def n(self) -> int:
        return int(self.bytes.shape[0])

    @property
    def width(self) -> int:
        return int(self.bytes.shape[1])


def make_keyset(keys: Sequence[Union[bytes, str, int]], max_key_len: int,
                int_mode: str = "uint64") -> KeySet:
    """Build a KeySet from python keys (bytes / str / int)."""
    rows = []
    lens = []
    for k in keys:
        if isinstance(k, str):
            k = k.encode("utf-8")
        if isinstance(k, (int, np.integer)):
            k = (encode_int64(int(k)) if int_mode == "int64"
                 else encode_uint64(int(k))).tobytes()
        if len(k) > max_key_len:
            raise ValueError(f"key longer than max_key_len={max_key_len}: {len(k)}")
        rows.append(k)
        lens.append(len(k))
    arr = np.zeros((len(rows), max_key_len), dtype=np.uint8)
    for i, r in enumerate(rows):
        arr[i, : len(r)] = np.frombuffer(r, dtype=np.uint8)
    return KeySet(arr, np.asarray(lens, dtype=np.int32))


def pack_words(kb: np.ndarray) -> np.ndarray:
    """Pack uint8 [.., L] into big-endian int32 words [.., ceil(L/4)].

    Packed words compare (as *unsigned*; we bias to keep int32 order correct)
    in the same order as the bytes, enabling O(L/4) lexsort keys.
    """
    n, L = kb.shape[0], kb.shape[-1]
    Lp = (L + 3) // 4 * 4
    if Lp != L:
        pad = np.zeros(kb.shape[:-1] + (Lp - L,), dtype=np.uint8)
        kb = np.concatenate([kb, pad], axis=-1)
    w = kb.reshape(kb.shape[:-1] + (Lp // 4, 4)).astype(np.uint32)
    words = (w[..., 0] << 24) | (w[..., 1] << 16) | (w[..., 2] << 8) | w[..., 3]
    # bias so that int32 ordering == unsigned ordering
    return (words.astype(np.int64) - (1 << 31)).astype(np.int32)


def pack_words_j(kb) -> "jnp.ndarray":
    """jnp version of :func:`pack_words` (order-preserving int32 words)."""
    import jax.numpy as jnp
    return jnp.stack(_word_cols_j(kb), axis=-1)


def lex_sort_indices(ks: KeySet) -> np.ndarray:
    """Indices that sort the KeySet lexicographically (bytes, then length)."""
    words = pack_words(ks.bytes)  # [N, W]
    cols = [ks.lens] + [words[:, i] for i in range(words.shape[1] - 1, -1, -1)]
    return np.lexsort(cols)


def _word_cols_j(kb) -> list:
    """The words of :func:`pack_words_j` as separate ``int32 [...]``
    columns, assembled from byte columns. Regrouping a narrow
    ``uint8 [N, L]`` matrix into ``[N, W, 4]`` makes the TPU compiler emit
    code that grows with ``N`` (minutes at 2^25 rows); byte columns
    compile in constant time."""
    import jax.numpy as jnp
    k = kb.astype(jnp.uint32)
    L = kb.shape[-1]
    cols = []
    for w in range(-(-L // 4)):
        word = jnp.zeros(kb.shape[:-1], jnp.uint32)
        for b in range(min(4, L - 4 * w)):
            word = word | (k[..., 4 * w + b] << (24 - 8 * b))
        cols.append((word ^ jnp.uint32(1 << 31)).astype(jnp.int32))
    return cols


def lex_sort_indices_j(kb, kl, invalid=None) -> "jnp.ndarray":
    """jnp twin of :func:`lex_sort_indices`: device argsort of padded keys by
    (bytes asc, length tie-break), optionally pushing rows flagged by the
    bool mask ``invalid`` past every valid row. Single definition of the
    device key order — the build and rebuild paths (DESIGN.md §5) must sort
    identically for host/device parity to hold.

    A least-significant-first chain of stable one-key argsorts inside a
    ``fori_loop``, which gives exactly ``jnp.lexsort``'s order (ties keep
    input order). One sort in a loop compiles in seconds at 2^25 rows,
    where a many-key comparator sort takes the TPU compiler many minutes.
    """
    import jax
    import jax.numpy as jnp
    cols = [kl.astype(jnp.int32)] + _word_cols_j(kb)[::-1]
    if invalid is not None:
        cols.append(invalid.astype(jnp.int32))  # most significant: valid first
    cols = jnp.stack(cols)              # [C, N], least significant first

    def one_key(c, perm):
        return jnp.take(perm, jnp.argsort(jnp.take(cols[c], perm),
                                          stable=True))

    n = kb.shape[0]
    return jax.lax.fori_loop(0, cols.shape[0], one_key,
                             jnp.arange(n, dtype=jnp.int32))


def compare_padded(a_bytes: np.ndarray, a_len: np.ndarray,
                   b_bytes: np.ndarray, b_len: np.ndarray) -> np.ndarray:
    """Vectorized 3-way compare (-1/0/1) on padded keys with length tie-break.

    Shapes broadcast on the leading dims; last dim is key width.
    Works for numpy and jax.numpy arrays alike.
    """
    xp = np  # both numpy & jnp expose the same API surface used here
    try:  # allow jnp arrays transparently
        import jax.numpy as jnp
        if any(hasattr(x, "aval") or type(x).__module__.startswith("jax")
               for x in (a_bytes, b_bytes)):
            xp = jnp
    except Exception:  # pragma: no cover
        pass
    a = a_bytes.astype(xp.int32)
    b = b_bytes.astype(xp.int32)
    diff = a - b
    nz = diff != 0
    # first nonzero byte position; width if all equal
    width = a.shape[-1]
    idx = xp.argmax(nz, axis=-1)
    anynz = nz.any(axis=-1)
    first = xp.where(anynz, xp.take_along_axis(diff, idx[..., None], axis=-1)[..., 0], 0)
    byte_cmp = xp.sign(first)
    len_cmp = xp.sign(a_len - b_len)
    return xp.where(anynz, byte_cmp, len_cmp).astype(xp.int32)


def fnv1a_tags(kb: np.ndarray, klen: np.ndarray) -> np.ndarray:
    """1-byte FNV-1a-style fingerprints over the valid bytes of each key.

    Vectorized and jnp-compatible: masked positions contribute the identity.
    Matches the role of ``tags`` in the paper's leaf nodes.
    """
    xp = np
    try:
        import jax.numpy as jnp
        if type(kb).__module__.startswith("jax"):
            xp = jnp
    except Exception:  # pragma: no cover
        pass
    L = kb.shape[-1]
    h = xp.full(kb.shape[:-1], 0x811C9DC5, dtype=xp.uint32)
    pos = xp.arange(L, dtype=xp.int32)
    for i in range(L):
        valid = (pos[i] < klen)
        byte = kb[..., i].astype(xp.uint32)
        nh = (h ^ byte) * xp.uint32(0x01000193)
        h = xp.where(valid, nh, h)
    # fold to one byte
    h = (h ^ (h >> 16)) & xp.uint32(0xFFFF)
    h = (h ^ (h >> 8)) & xp.uint32(0xFF)
    return h.astype(xp.uint8)
