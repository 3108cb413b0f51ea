"""The program's ``ssd_scan`` Pallas kernel (Mamba-2 chunked SSD forward):
the operations and bytes one call needs, from its shapes.

One call covers B*H (batch, head) rows of S/Q chunks.  Per batch row and
chunk the algorithm needs the (Q, Q) scores C B^T once (2 Q^2 N; one B and C
serve every head, though the kernel forms them per head); per head and chunk
the product of the masked decay-weighted scores with dt*x (2 Q^2 P), the
carried state's output C h (2 Q N P) and the state update B^T (dt decay x)
(2 Q N P).  Elementwise work
(the segment sums, exp, masks) is left out: it does not run on the matrix
unit and is small beside these.

The bytes are each operand read once and each result written once: x and y
(B, S, H, P) in the model dtype, dt and dt*A (B*H, S) in float32, B and C
(B, S, N) in the model dtype, the final state (B, H, N, P) in float32.
"""
from __future__ import annotations


def flops(B: int, S: int, H: int, P: int, N: int, Q: int, **_) -> float:
    nc = S // Q
    return float(B * nc * 2 * Q * Q * N + B * H * nc * (2 * Q * Q * P + 4 * Q * N * P))


def bytes_moved(B: int, S: int, H: int, P: int, N: int, Q: int,
                x_bytes: int = 2, bc_bytes: int = 2, **_) -> float:
    xy = 2 * B * S * H * P * x_bytes
    dt_a = 2 * B * H * S * 4
    bc = 2 * B * S * N * bc_bytes
    state = B * H * N * P * 4
    return float(xy + dt_a + bc + state)


def least_time(shape: dict, peak_flops: float, peak_bytes: float) -> tuple[float, str]:
    """Seconds one call takes at the roofline, and which bound sets it."""
    tf, tb = flops(**shape) / peak_flops, bytes_moved(**shape) / peak_bytes
    return (tf, "flops") if tf >= tb else (tb, "bytes")
