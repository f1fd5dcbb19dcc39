"""AOT-compile device programs for a chip this sandbox does not have.

libtpu ships the compiler, so `jax.experimental.topologies` can hand out
a `v5e:2x2` topology descriptor on a CPU-only host and `.lower().compile()`
runs Mosaic + XLA:TPU against it. A PR learns "Mosaic refuses this
kernel" or "this resize bucket no longer fits" here, at no chip cost.
Slow lane only: the programs take roughly 17 s + 11 s to compile, the
two widest resize calls (ISSUE 38) about 17 s each.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import jax, numpy as np
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from spacedrive_tpu.ops import blake3_jax, thumbnail_jax

topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
assert len(topo.devices) == 4, topo.devices
one = SingleDeviceSharding(topo.devices[0])

def spec(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

# the 32-row rung of the 57-chunk hot bucket, Pallas chunk stage
hashed = blake3_jax._hash_batch_impl_modes["tpu"].lower(
    spec((32, 57 * 256), np.uint32), spec((32,), np.int32), max_chunks=57
).compile()
assert hashed.out_info.shape == (32, 8), hashed.out_info
assert hashed.memory_analysis().generated_code_size_in_bytes > 0

# one resize bucket: 4 canvases of 1024², the colour planes and the alpha
# plane, planes folded into the row → the 512 × 1024 output canvas,
# through the one jitted function
for planes in (3, 1):
    resized = thumbnail_jax._resize_fn().lower(
        spec((4, 1024, 1024 * planes), np.uint8), spec((4, 2), np.float32),
        out_hw=thumbnail_jax.OUT_CANVAS_HW, planes=planes,
    ).compile()
    assert resized.out_info.shape == (4, 512, 1024 * planes), resized.out_info
    # canvases + scales in, one output canvas each out — and it fits a chip
    mem = resized.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16 << 30

# the widest calls the byte bound lets through (ISSUE 38): a panorama's
# canvases into the second output canvas, and the largest canvas there is
for (bh, bw), out_hw in (((4096, 16384), thumbnail_jax.OUT_CANVAS_WIDE_HW),
                         ((16384, 16384), thumbnail_jax.OUT_CANVAS_HW)):
    pad = thumbnail_jax.call_rows(bh, bw, 3)
    assert pad * bh * bw * 3 <= thumbnail_jax.CALL_CANVAS_BYTES
    resized = thumbnail_jax._resize_fn().lower(
        spec((pad, bh, bw * 3), np.uint8), spec((pad, 2), np.float32),
        out_hw=out_hw, planes=3,
    ).compile()
    assert resized.out_info.shape == (pad, out_hw[0], out_hw[1] * 3)
    mem = resized.memory_analysis()
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes) < 8 << 30  # 3.6 and 5.4 GB of 16
print("AOT_OK")
"""


@pytest.mark.slow
def test_hash_and_resize_programs_compile_for_v5e():
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
        TPU_ACCELERATOR_TYPE="v5litepod-4", TPU_WORKER_HOSTNAMES="localhost",
    )
    out = subprocess.run(
        [sys.executable, "-c", _CHILD], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    assert "AOT_OK" in out.stdout
