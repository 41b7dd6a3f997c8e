"""Expert routes on the wire: the `kubeai_routes` blocks of a response.

A request that sets `"kubeai_routes": true` on `/v1/completions` or
`/v1/chat/completions` gets, for every position the program computed for
it, the expert set each routed layer took. The engine hands them over as
`StepEvent.routes`, blocks `(start, rows)` with `rows` a `[n, routed
layers, k]` array of global expert ids; this module turns such blocks
into the JSON objects the server sends and back. No JAX here: a client
needs only `base64` and the five keys.

    {"start": 37, "rows": 2, "shape": [16, 2], "dtype": "uint8",
     "data": "<base64>"}

`data` is `rows * shape[0] * shape[1]` ids of `dtype` (`uint8`, `uint16`
or `uint32`, little-endian), row-major: row j is position `start + j`,
inside it routed layer l's `k` experts in the router's order, best
first. docs/concepts/expert-routes.md has the rules for which rows a
request gets.

A family that generates by diffusion over blocks routes its rows anew at
every forward, so it hands over FORWARDS (`"kubeai_forwards": true`,
docs/concepts/block-diffusion.md). A chunk of a stream carries many of them
(10 for 8 tokens), so they are packed: one object for all the forwards a
chunk carries, in the order they ran,

    {"forwards": 10, "shape": [7, 8], "dtype": "uint8", "data": "<base64>"}

`data` is, a forward, a header of little-endian uint32 (`start`, `rows`,
`n`, then the `n` offsets of the rows that forward committed, then the `n`
tokens it committed them to) followed by its `rows * shape[0] * shape[1]`
expert ids of `dtype`, laid out as a `kubeai_routes` block's.
"""

from __future__ import annotations

import base64

import numpy as np

WIRE_DTYPES = ("uint8", "uint16", "uint32")


def join_blocks(blocks) -> list:
    """The blocks in the order given, every run of blocks that touch (one
    starts where the one before ends: a decode step's row after the row
    before) joined into one. A gap, a repeat or a step back (rows
    recomputed after a preemption) starts a new block: a consumer sees
    it as it happened."""
    runs: list[list] = []  # [start, parts, end]
    for start, rows in blocks:
        if runs and runs[-1][2] == start:
            runs[-1][1].append(rows)
            runs[-1][2] += len(rows)
        else:
            runs.append([int(start), [rows], int(start) + len(rows)])
    return [
        (start, parts[0] if len(parts) == 1 else np.concatenate(parts))
        for start, parts, _ in runs
    ]


def encode_block(start: int, rows: np.ndarray) -> dict:
    """One `(start, rows [n, routed layers, k])` block as its JSON object."""
    if rows.dtype.name not in WIRE_DTYPES or rows.ndim != 3:
        raise ValueError(f"not a block of expert ids: {rows.dtype} {rows.shape}")
    little = rows.astype(rows.dtype.newbyteorder("<"), order="C")
    return {
        "start": int(start),
        "rows": int(rows.shape[0]),
        "shape": [int(rows.shape[1]), int(rows.shape[2])],
        "dtype": rows.dtype.name,
        "data": base64.b64encode(little.tobytes()).decode("ascii"),
    }


def encode_forwards(forwards) -> dict:
    """Forwards `(start, rows [n, routed layers, k], commit, tokens)` of a
    family that generates by blocks, in the order they ran, as one JSON
    object (the module's docstring has the layout)."""
    first = forwards[0][1]
    if first.dtype.name not in WIRE_DTYPES or first.ndim != 3:
        raise ValueError(f"not expert ids: {first.dtype} {first.shape}")
    little = first.dtype.newbyteorder("<")
    parts = []
    for start, rows, commit, tokens in forwards:
        parts.append(np.asarray(
            [start, len(rows), len(commit), *commit, *tokens], "<u4").tobytes())
        parts.append(rows.astype(little, order="C").tobytes())
    return {
        "forwards": len(forwards),
        "shape": [int(first.shape[1]), int(first.shape[2])],
        "dtype": first.dtype.name,
        "data": base64.b64encode(b"".join(parts)).decode("ascii"),
    }


def decode_forwards(block: dict) -> list:
    """The inverse: `(start, rows, commit, tokens)` a forward. Raises
    ValueError on an object whose bytes are not what its header says."""
    if block["dtype"] not in WIRE_DTYPES:
        raise ValueError(f"unknown route dtype {block['dtype']!r}")
    dtype = np.dtype(block["dtype"]).newbyteorder("<")
    layers, k = block["shape"]
    raw = base64.b64decode(block["data"])
    out, at = [], 0
    for _ in range(block["forwards"]):
        start, rows, n = (int(x) for x in np.frombuffer(raw, "<u4", 3, at))
        marks = np.frombuffer(raw, "<u4", 2 * n, at + 12)
        at += 12 + 8 * n
        size = rows * layers * k * dtype.itemsize
        ids = np.frombuffer(raw[at:at + size], dtype).reshape(rows, layers, k)
        at += size
        out.append((start, ids.astype(block["dtype"]),
                    tuple(marks[:n].tolist()), tuple(marks[n:].tolist())))
    if at != len(raw):
        raise ValueError(f"{len(raw)} bytes of forwards, {at} accounted for")
    return out


def decode_block(block: dict) -> tuple[int, np.ndarray]:
    """The inverse: `(start, rows [n, routed layers, k])`. Raises
    ValueError on a block whose bytes are not what its header says."""
    if block["dtype"] not in WIRE_DTYPES:
        raise ValueError(f"unknown route dtype {block['dtype']!r}")
    dtype = np.dtype(block["dtype"]).newbyteorder("<")
    layers, k = block["shape"]
    raw = base64.b64decode(block["data"])
    if len(raw) != block["rows"] * layers * k * dtype.itemsize:
        raise ValueError(
            f"route block of {len(raw)} bytes, header says "
            f"{block['rows']} x {layers} x {k} x {dtype.itemsize}"
        )
    rows = np.frombuffer(raw, dtype).reshape(block["rows"], layers, k)
    return int(block["start"]), rows.astype(block["dtype"])
