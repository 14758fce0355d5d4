"""The vertical counting forms' item-major bitmap, built on the device.

``MapReduceRuntime`` places the zero-padded rows sharded over ``data`` and
each chip lays out its own split in one shard_map; the bytes must be those
of the host reference ``bitset.vertical_pack`` applied shard by shard.
"""

import numpy as np
import pytest

from test_multidevice import run_py


@pytest.mark.parametrize("n_data,n_cand,n,n_items", [
    (1, 1, 1037, 70),     # n not a multiple of 32
    (1, 1, 13, 33),       # n < 32
    (1, 1, 200, 64),      # n_items = 32·W exactly
    (4, 1, 203, 40),      # rows padded to the data multiple, 51 a shard
    (4, 1, 70, 96),       # each shard under 32 rows, n_items = 32·W
    (2, 2, 101, 50),      # a cand axis holds a copy of each data shard
])
def test_device_layout_is_vertical_pack_per_shard(n_data, n_cand, n,
                                                  n_items):
    out = run_py(f"""
        import jax, numpy as np
        from jax.sharding import Mesh
        from repro.core.bitset import n_words, vertical_pack
        from repro.core.mapreduce import MapReduceRuntime
        n_data, n_cand, n, n_items = {n_data}, {n_cand}, {n}, {n_items}
        devs = np.array(jax.devices()[:n_data * n_cand])
        mesh = Mesh(devs.reshape(n_data, n_cand), ("data", "cand"))
        rng = np.random.default_rng(n)
        db = rng.integers(0, 2**32, (n, n_words(n_items)),
                          dtype=np.uint64).astype(np.uint32)
        db[:, -1] &= np.uint32((1 << (n_items - 32 * (db.shape[1] - 1))) - 1
                               if n_items % 32 else 0xFFFFFFFF)
        rt = MapReduceRuntime(mesh=mesh, impl="vertical", autotune=False,
                              cand_axis="cand" if n_cand > 1 else None)
        placed = rt.scatter_db(db, n_items=n_items)
        padded = np.concatenate(
            [db, np.zeros(((-n) % n_data, db.shape[1]), np.uint32)])
        per = padded.shape[0] // n_data
        want = np.stack([vertical_pack(padded[i * per:(i + 1) * per],
                                       n_items) for i in range(n_data)])
        got = np.asarray(placed)
        assert got.dtype == want.dtype == np.uint32
        assert got.shape == want.shape == (n_data, n_items + 1,
                                           -(-per // 32)), got.shape
        assert got.tobytes() == want.tobytes()
        # every chip holds its own data shard, whatever its cand index
        assert len(placed.addressable_shards) == n_data * n_cand
        for shard in placed.addressable_shards:
            i = shard.index[0].start or 0
            assert np.asarray(shard.data).tobytes() == want[i:i + 1].tobytes()
        assert rt.stats.device_layouts == 1
        assert rt.stats.bytes_to_device == padded.nbytes * n_cand
        print("LAYOUT_OK")
    """, n_devices=4)
    assert "LAYOUT_OK" in out


def _mine_twice(impl, monkeypatch):
    from repro.core import mine
    from repro.core import mapreduce
    from repro.core.bitset import pack_itemsets
    from repro.obs import Tracer, use_tracer
    traced = []
    real = mapreduce.jvertical_pack

    def counting(rows, n_items):
        traced.append(rows.shape)
        return real(rows, n_items)
    monkeypatch.setattr(mapreduce, "jvertical_pack", counting)
    rng = np.random.default_rng(7)
    db = pack_itemsets([sorted(set(rng.integers(0, 12, 5).tolist()))
                        for _ in range(90)], 12)
    rt = mapreduce.MapReduceRuntime(impl=impl, autotune=False)
    tr = Tracer()
    with use_tracer(tr):
        for _ in range(2):
            mine(db_masks=db, n_items=12, min_sup=0.2, runtime=rt,
                 elastic=False)
    spans = [s for s in tr.spans if s.name == "mine.scatter"]
    return rt, spans, traced


def test_two_mines_build_the_layout_program_once(monkeypatch):
    from repro.obs import Registry, get_registry, set_registry
    prev = get_registry()
    reg = set_registry(Registry())
    try:
        rt, spans, traced = _mine_twice("vertical", monkeypatch)
    finally:
        set_registry(prev)
    assert traced == [(90, 1)]            # traced, so compiled, once
    assert len(rt._layouts) == 1
    assert rt.stats.device_layouts == 2
    assert reg.value("mine.device_layouts") == 2
    assert [s.attrs["layout"] for s in spans] == ["device", "device"]
    assert all(s.attrs["pack_seconds"] > 0 for s in spans)


@pytest.mark.parametrize("impl", ["jnp", "matmul", "pallas_interpret"])
def test_horizontal_forms_lay_out_nothing_on_the_device(impl, monkeypatch):
    rt, spans, traced = _mine_twice(impl, monkeypatch)
    assert traced == [] and rt._layouts == {}
    assert rt.stats.device_layouts == 0
    assert [s.attrs["layout"] for s in spans] == ["host", "host"]
    assert all(s.attrs["pack_seconds"] == 0 for s in spans)
