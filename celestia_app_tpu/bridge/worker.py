"""The persistent XLA runtime worker behind the C bridge.

Run as `python -m celestia_app_tpu.bridge.worker`; speaks the bridge's
length-prefixed binary protocol on stdin/stdout (see
bridge/celestia_square_bridge.cpp).  Holds jitted pipelines per square size;
the warmup op compiles ahead of time so extend requests never pay a compile
on the consensus critical path (SURVEY §7 hard part 4).
"""

from __future__ import annotations

import os
import struct
import sys

# The worker takes JAX's default platform: behind a Go host (which never
# touches JAX) it is the one process that holds the chip.
# $CELESTIA_BRIDGE_PLATFORM pins another platform (tests pin "cpu"); the
# pipeline is integer-only, so the output is bit-identical either way.
if os.environ.get("CELESTIA_BRIDGE_PLATFORM"):
    os.environ["JAX_PLATFORMS"] = os.environ["CELESTIA_BRIDGE_PLATFORM"]

REQ_MAGIC = 0x31515343  # "CSQ1"
RESP_MAGIC = 0x52515343  # "CSQR"
OP_EXTEND = 1
OP_PING = 2
OP_WARMUP = 3
OP_SHUTDOWN = 4

SHARE_SIZE = 512


def _respond(out, status: int, payload: bytes = b"") -> None:
    out.write(struct.pack("<IIQ", RESP_MAGIC, status, len(payload)))
    if payload:
        out.write(payload)
    out.flush()


def _extend(ods_bytes: bytes, k: int) -> bytes:
    import numpy as np

    from celestia_app_tpu.da.eds import ExtendedDataSquare

    ods = np.frombuffer(ods_bytes, dtype=np.uint8).reshape(k, k, SHARE_SIZE)
    eds = ExtendedDataSquare.compute(ods)
    return (
        np.asarray(eds.squared()).tobytes()
        + b"".join(eds.row_roots())
        + b"".join(eds.col_roots())
        + eds.data_root()
    )


def _warmup(k: int) -> None:
    import numpy as np

    from celestia_app_tpu.da.eds import ExtendedDataSquare

    ods = np.zeros((k, k, SHARE_SIZE), dtype=np.uint8)
    ExtendedDataSquare.compute(ods).data_root()


def main() -> int:
    from celestia_app_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()

    stdin = sys.stdin.buffer
    stdout = sys.stdout.buffer
    # Anything the runtime prints must not corrupt the protocol stream.
    sys.stdout = sys.stderr

    while True:
        header = stdin.read(20)
        if len(header) < 20:
            return 0  # parent closed the pipe
        magic, op, k, payload_len = struct.unpack("<IIIQ", header)
        if magic != REQ_MAGIC:
            return 1
        payload = stdin.read(payload_len) if payload_len else b""
        if payload_len and len(payload) < payload_len:
            return 1

        if op == OP_PING:
            _respond(stdout, 0)
        elif op == OP_WARMUP:
            try:
                _warmup(k)
                _respond(stdout, 0)
            except Exception:
                _respond(stdout, 1)
        elif op == OP_EXTEND:
            try:
                if len(payload) != k * k * SHARE_SIZE:
                    raise ValueError("payload size mismatch")
                _respond(stdout, 0, _extend(payload, k))
            except Exception:
                _respond(stdout, 1)
        elif op == OP_SHUTDOWN:
            _respond(stdout, 0)
            return 0
        else:
            _respond(stdout, 2)


if __name__ == "__main__":
    sys.exit(main())
