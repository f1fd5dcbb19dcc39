"""A closed library leaves nothing of itself in the process (ISSUE 28):
`object/search/index._INDEXES` kept a `LibraryIndex`, and through it the
library and its `Node`, for every node a process had shut down, ≈ 0.5 GiB
a pass of `photolib.cold` (PERF.md §6, PR 28)."""

import asyncio
import gc
import os
import weakref

import numpy as np
import pytest
from PIL import Image

from spacedrive_tpu import cli
from spacedrive_tpu.api.search import search_semantic
from spacedrive_tpu.node import Node
from spacedrive_tpu.object.search import index as search_index

IMAGES = 4


def _location(root) -> str:
    loc = str(root / "location")
    os.makedirs(loc)
    rng = np.random.default_rng(28)
    yy, xx = np.mgrid[0:48, 0:64] / 48.0
    for i in range(IMAGES):
        a, b, c = rng.uniform(-3, 3, 3)
        field = np.stack([np.sin(a * xx + b * yy + c + k) * 0.5 + 0.5
                          for k in range(3)], -1)
        Image.fromarray((field * 255).astype(np.uint8)).save(
            os.path.join(loc, f"img{i}.png"))
    return loc


async def _started(data_dir: str) -> Node:
    node = Node(data_dir, use_device=True)
    node.config.config.p2p.enabled = False
    await node.start()
    return node


def _keys_of(lib_id: str) -> list:
    return [k for k in search_index._INDEXES if k[1] == lib_id]


def _top_names(lib, probe: str) -> list[str]:
    out = search_semantic(lib, {"query": probe, "take": IMAGES})
    assert out["resolved"] is True
    return [n["name"] for n in out["nodes"]]


@pytest.mark.parametrize("how", ["shutdown", "close_library"])
def test_closed_library_drops_its_index_and_frees_the_node(tmp_path, how):
    location, data_dir = _location(tmp_path), str(tmp_path / "node")
    probe = os.path.join(location, "img2.png")

    async def first_life():
        node = await _started(data_dir)
        try:
            summary = await cli.index_location(node, location, "lib", "tpu")
            lib = next(iter(node.libraries.libraries.values()))
            names = _top_names(lib, probe)
            assert _keys_of(summary["library_id"])  # the index is live
            if how == "close_library":
                await node.close_library(lib.id)
                assert not _keys_of(summary["library_id"])
        finally:
            await node.shutdown()
        return weakref.ref(node), weakref.ref(lib), summary, names

    node_ref, lib_ref, summary, names = asyncio.run(first_life())
    assert names[0] == "img2" and len(names) == IMAGES
    assert not _keys_of(summary["library_id"])
    gc.collect()
    assert lib_ref() is None, "the closed library is still referenced"
    assert node_ref() is None, "the shut-down node is still referenced"

    async def second_life():
        node = await _started(data_dir)
        try:
            lib = next(iter(node.libraries.libraries.values()))
            assert str(lib.id) == summary["library_id"]
            return _top_names(lib, probe)  # built again from the rows
        finally:
            await node.shutdown()

    assert asyncio.run(second_life()) == names
    assert not _keys_of(summary["library_id"])
