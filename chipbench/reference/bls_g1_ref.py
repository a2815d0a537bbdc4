"""Plain BLS12-381 G1 arithmetic — the oracle for aggregate signatures.

The sum of a QC's vote signatures is the one number a compact QC
carries, and the device computes it (``hotstuff_tpu.tpu.bls``) in
Montgomery limbs with complete projective formulas.  This module
computes the same sum the plainest way there is, and shares no code
with ``crypto/bls/`` or ``tpu/bls.py``: Python integers, affine
coordinates, every inverse and square root a ``pow``.  A point is
``None`` (the identity) or a pair ``(x, y)`` of integers mod ``P``.

Serialization is the 48-byte compressed G1 encoding of ZCash's BLS12-381
format, which the IETF BLS signature draft adopts: the x coordinate
big-endian, with three flag bits in the top bits of the first byte
(0x80 compressed, 0x40 the identity, 0x20 the larger of the two y).
Departures from that format and from the IETF scheme, each as
``crypto/bls/curve.py`` writes and reads its points:

- ``decompress`` checks that the point lies on the curve, not that it
  lies in the prime-order subgroup: a QC's signatures are summed
  unchecked and the verifier checks the aggregate once, so the oracle
  sums what the program sums.
- the identity must be exactly ``0xC0`` followed by 47 zero bytes; a
  set sign bit there is refused, as the format asks;
- hashing to the curve is the program's own map, not the IETF
  hash-to-curve suite, and is not needed here: the oracle sums
  signatures, it does not make them.
"""

from __future__ import annotations

#: the base field's modulus
P = 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB
#: the curve y^2 = x^3 + B
B = 4
#: the generator of G1
G = (
    0x17F1D3A73197D7942695638C4FA9AC0FC3688C4F9774B905A14E3A3F171BAC586C55E83FF97A1AEFFB3AF00ADB22C6BB,
    0x08B3F481E3AAA0F1A09E30ED741D8AE4FCF5E095D5D00AF600DB18CB2C04B3EDD03CC744A2888AE40CAA232946C5E7E1,
)

_COMPRESSED, _INFINITY, _LARGER = 0x80, 0x40, 0x20


def on_curve(point) -> bool:
    if point is None:
        return True
    x, y = point
    return (y * y - x * x * x - B) % P == 0


def add(p1, p2):
    """``p1 + p2`` by the chord and tangent, ``None`` for the identity."""
    if p1 is None:
        return p2
    if p2 is None:
        return p1
    x1, y1 = p1
    x2, y2 = p2
    if x1 == x2:
        if (y1 + y2) % P == 0:
            return None
        return double(p1)
    slope = (y2 - y1) * pow(x2 - x1, P - 2, P) % P
    x3 = (slope * slope - x1 - x2) % P
    return x3, (slope * (x1 - x3) - y1) % P


def double(point):
    if point is None or point[1] == 0:
        return None
    x, y = point
    slope = 3 * x * x * pow(2 * y, P - 2, P) % P
    x3 = (slope * slope - 2 * x) % P
    return x3, (slope * (x - x3) - y) % P


def total(points):
    """The sum of a list of points, left to right."""
    out = None
    for point in points:
        out = add(out, point)
    return out


def compress(point) -> bytes:
    if point is None:
        return bytes([_COMPRESSED | _INFINITY]) + bytes(47)
    x, y = point
    out = bytearray(x.to_bytes(48, "big"))
    out[0] |= _COMPRESSED | (_LARGER if y > (P - 1) // 2 else 0)
    return bytes(out)


def decompress(data: bytes):
    """The point of a 48-byte encoding; raises ``ValueError`` for bytes
    that are not one (no compression flag, a malformed identity, x not
    below ``P``, or no y on the curve)."""
    if len(data) != 48 or not data[0] & _COMPRESSED:
        raise ValueError("not a compressed G1 encoding")
    if data[0] & _INFINITY:
        if data[0] != _COMPRESSED | _INFINITY or any(data[1:]):
            raise ValueError("malformed identity")
        return None
    x = int.from_bytes(bytes([data[0] & 0x1F]) + data[1:], "big")
    if x >= P:
        raise ValueError("x is not below the modulus")
    rhs = (x * x * x + B) % P
    y = pow(rhs, (P + 1) // 4, P)  # P = 3 mod 4
    if y * y % P != rhs:
        raise ValueError("x is on no point of the curve")
    if (y > (P - 1) // 2) != bool(data[0] & _LARGER):
        y = P - y
    return x, y


def sum_compressed(encodings) -> bytes:
    """The compressed sum of compressed points: what a compact QC's
    aggregate signature must equal, given its signers' vote
    signatures."""
    return compress(total(decompress(e) for e in encodings))


__all__ = [
    "B", "G", "P", "add", "compress", "decompress", "double", "on_curve",
    "sum_compressed", "total",
]
