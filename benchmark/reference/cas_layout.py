"""The cas_id message layout, restated from upstream Spacedrive
(core/src/object/cas.rs as BASELINE.md cites it) so that neither the
generators nor the comparison lean on `spacedrive_tpu.ops.cas` for
what they check: the file size as 8 little-endian bytes, then the
whole file up to 100 KiB, else an 8 KiB header, four 10 KiB samples at
equal jumps and an 8 KiB footer (57,352 bytes). The cas_id is the first
16 hex characters of the message's BLAKE3 digest.
"""

from __future__ import annotations

import os
import struct

MINIMUM_FILE_SIZE = 100 * 1024
HEADER_OR_FOOTER_SIZE = 8 * 1024
SAMPLE_SIZE = 10 * 1024
SAMPLE_COUNT = 4
SAMPLED_MESSAGE_LEN = (8 + 2 * HEADER_OR_FOOTER_SIZE
                       + SAMPLE_COUNT * SAMPLE_SIZE)  # 57,352
CAS_HEX = 16


def ranges(size: int) -> list[tuple[int, int]]:
    """(offset, length) ranges of a file that its cas_id reads."""
    if size <= MINIMUM_FILE_SIZE:
        return [(0, size)]
    jump = (size - 2 * HEADER_OR_FOOTER_SIZE) // SAMPLE_COUNT
    return ([(0, HEADER_OR_FOOTER_SIZE)]
            + [(HEADER_OR_FOOTER_SIZE + k * jump, SAMPLE_SIZE)
               for k in range(SAMPLE_COUNT)]
            + [(size - HEADER_OR_FOOTER_SIZE, HEADER_OR_FOOTER_SIZE)])


def message_len(size: int) -> int:
    return 8 + size if size <= MINIMUM_FILE_SIZE else SAMPLED_MESSAGE_LEN


def message(path: str) -> bytes:
    size = os.path.getsize(path)
    parts = [struct.pack("<Q", size)]
    with open(path, "rb") as f:
        for off, ln in ranges(size):
            f.seek(off)
            parts.append(f.read(ln))
    return b"".join(parts)
