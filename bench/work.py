"""The work one budgeted-SGD training step needs: operations and HBM bytes
counted from the algorithm, not from how the program does it.

Shapes: ``C`` one-vs-rest classes, ``S`` slots per class (budget + batch),
``d`` features, ``B`` rows per step; all values float32 (4 bytes).

Per step, for every class:
  * margin rows: k(x_b, s_j) for the B rows against the S slots, by
    ||x||^2 + ||s||^2 - 2 x.s and one exp: ``B*S*(2d + 4)`` operations;
    the margin itself, ``2*B*S``; the rows' own Gram block ``B*B*(2d + 4)``.
    Bytes: the bank is read once (``S*d*4``), alpha read and written
    (``2*S*4``), the batch read once for all classes (``B*d*4``).
  * Pegasos shrink: ``S`` operations (alpha already read above).
Per inserted violator: its row written to the bank (``d*4``) and its
kernel row and column to the cache (``2*S*4``).
Per maintenance event: candidate scoring over the slots (mass ratio, two
bilinear lookups, the weighted degradation; ``20*S`` operations) reading
alpha and one cached kernel row (``2*S*4``); the merged point (``3d``
operations, ``d*4`` written) and its kernel row from the two parents' rows
(``6*S`` operations; two rows read, one row and one column written:
``4*S*4``).
"""
from __future__ import annotations

F32 = 4


def step_work(*, C: int, S: int, d: int, B: int) -> tuple[float, float]:
    """(operations, bytes) of one step before inserts and merges."""
    ops = C * (B * S * (2 * d + 4) + 2 * B * S + B * B * (2 * d + 4) + S)
    byt = C * (S * d + 2 * S) * F32 + B * d * F32
    return float(ops), float(byt)


def insert_work(*, S: int, d: int) -> tuple[float, float]:
    return 0.0, float((d + 2 * S) * F32)


def merge_work(*, S: int, d: int) -> tuple[float, float]:
    return float(20 * S + 3 * d + 6 * S), float((2 * S + d + 4 * S) * F32)


def window_work(*, steps: int, merges: int, inserts: int, C: int, S: int,
                d: int, B: int) -> dict:
    """Operations and bytes of a window that ran ``steps`` steps with
    ``merges`` maintenance events and ``inserts`` inserted rows in all."""
    so, sb = step_work(C=C, S=S, d=d, B=B)
    io, ib = insert_work(S=S, d=d)
    mo, mb = merge_work(S=S, d=d)
    return {"ops": steps * so + inserts * io + merges * mo,
            "bytes": steps * sb + inserts * ib + merges * mb,
            "steps": steps}
