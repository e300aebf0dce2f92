"""AES-128 (FIPS-197) and CTR mode (NIST SP 800-38A) in NumPy, written
from the standards: the S-box is built from the inverse in GF(2^8) and
the affine map, not copied.  Vectorised over blocks."""
from __future__ import annotations

import numpy as np

ROUNDS = 10


def _gmul(a: int, b: int) -> int:
    p = 0
    for _ in range(8):
        if b & 1:
            p ^= a
        a = ((a << 1) ^ (0x11B if a & 0x80 else 0)) & 0xFF
        b >>= 1
    return p


def _sbox() -> np.ndarray:
    inv = [0] * 256
    for a in range(1, 256):
        inv[a] = next(b for b in range(1, 256) if _gmul(a, b) == 1)
    out = np.zeros(256, np.uint8)
    for a in range(256):
        x, s = inv[a], inv[a]
        for _ in range(4):
            x = ((x << 1) | (x >> 7)) & 0xFF
            s ^= x
        out[a] = s ^ 0x63
    return out


SBOX = _sbox()
XTIME = np.array([_gmul(a, 2) for a in range(256)], np.uint8)
# state byte 4*c + r is row r of column c; ShiftRows moves row r left by r
SHIFT_ROWS = np.array([4 * ((c + r) % 4) + r for c in range(4) for r in range(4)])


def expand_key(key: bytes) -> np.ndarray:
    """The 11 round keys of AES-128, (11, 16) uint8."""
    if len(key) != 16:
        raise ValueError("AES-128 takes a 16-byte key")
    w = [np.frombuffer(key, np.uint8)[4 * i:4 * i + 4].copy() for i in range(4)]
    rcon = 1
    for i in range(4, 4 * (ROUNDS + 1)):
        t = w[i - 1].copy()
        if i % 4 == 0:
            t = SBOX[np.roll(t, -1)]
            t[0] ^= rcon
            rcon = _gmul(rcon, 2)
        w.append(w[i - 4] ^ t)
    return np.stack(w).reshape(ROUNDS + 1, 16)


def _mix_columns(s: np.ndarray) -> np.ndarray:
    a = s.reshape(-1, 4, 4)
    b = XTIME[a]
    r = np.roll
    # 2*a0 + 3*a1 + a2 + a3 on each row of every column
    out = b ^ r(a, -1, axis=2) ^ r(b, -1, axis=2) ^ r(a, -2, axis=2) ^ r(a, -3, axis=2)
    return out.reshape(-1, 16)


def encrypt_blocks(blocks: np.ndarray, round_keys: np.ndarray, rounds: int = ROUNDS) -> np.ndarray:
    """AES encryption of (N, 16) uint8 blocks."""
    s = blocks ^ round_keys[0]
    for rnd in range(1, rounds):
        s = _mix_columns(SBOX[s][:, SHIFT_ROWS]) ^ round_keys[rnd]
    return SBOX[s][:, SHIFT_ROWS] ^ round_keys[rounds]


def ctr_encrypt(payloads: np.ndarray, key: bytes, first_counters: np.ndarray) -> np.ndarray:
    """CTR encryption of equal-length payloads, (M, L) uint8; payload m
    starts at counter ``first_counters[m]``; a partial last block uses the
    front of its keystream block."""
    m, length = payloads.shape
    nb = -(-length // 16)
    starts = np.asarray(first_counters, np.uint64)
    # counter blocks: a 96-bit zero nonce, then the 32-bit big-endian counter
    # first + 0 .. first + nb - 1 of each payload
    all_ctr = (starts[:, None] + np.arange(nb, dtype=np.uint64)[None, :]).astype(np.uint32)
    ctr = np.zeros((m * nb, 16), np.uint8)
    ctr[:, 12:] = all_ctr.reshape(-1).astype(">u4").view(np.uint8).reshape(-1, 4)
    ks = encrypt_blocks(ctr, expand_key(key)).reshape(m, nb * 16)[:, :length]
    return payloads ^ ks
