"""Tile-granular serving tests: byte-budgeted content-deduplicating cache,
bitwise tile-path equivalence (assembly, strips, partial renders), dirty-row
invalidation, and the cache-key resolution regression."""
import numpy as np
import pytest

from repro.core import projection as P
from repro.core.config import GSConfig
from repro.core.sharding import make_mesh
from repro.core.train import make_batched_eval_render, make_tile_row_render
from repro.serve_gs import (
    FrameCache,
    RenderServer,
    frame_key,
    make_clients,
    stack_cameras,
    tile_key,
)

from conftest import make_cam, make_scene

H = W = 32


def _server(g=None, *, size=H, **kw):
    g = g if g is not None else make_scene(n=256, scale=0.06)
    cfg = GSConfig(img_h=size, img_w=size, k_per_tile=64)
    kw.setdefault("n_levels", 1)
    kw.setdefault("max_batch", 4)
    return RenderServer(g, cfg, **kw)


# ==================================================================== cache
def test_cache_byte_budget_evicts_lru():
    tile = np.zeros((4, 4, 3), np.float32)  # 192 bytes
    c = FrameCache(capacity_bytes=2 * tile.nbytes, dedup=False)
    c.put(("a",), tile.copy())
    c.put(("b",), tile.copy())
    assert c.bytes == 2 * tile.nbytes and len(c) == 2
    assert c.get(("a",)) is not None  # "a" becomes most-recent
    c.put(("c",), tile.copy())  # budget forces "b" (least recent) out
    assert c.get(("b",)) is None and c.get(("c",)) is not None
    s = c.stats()
    assert s["evictions"] == 1 and s["bytes"] == 2 * tile.nbytes


def test_cache_content_dedup_shares_identical_tiles():
    """Identical tile CONTENT is stored once: the background tiles shared by
    every pose of an orbit cost one buffer, not one per pose."""
    bg = np.zeros((4, 4, 3), np.float32)
    c = FrameCache(capacity_bytes=10 * bg.nbytes)
    for i in range(8):
        c.put(("pose", i), bg.copy())
    s = c.stats()
    assert len(c) == 8
    assert s["unique_buffers"] == 1 and s["bytes"] == bg.nbytes
    assert s["dedup_shared"] == 7 and s["dedup_bytes_saved"] == 7 * bg.nbytes
    # deduped entries really alias one read-only buffer
    assert c.get(("pose", 0)) is c.get(("pose", 5))
    # dropping one referencing key keeps the buffer for the others
    c.drop(lambda k: k[1] == 0)
    assert c.bytes == bg.nbytes and c.get(("pose", 1)) is not None


def test_cache_drop_is_accounted_separately_from_eviction():
    """Satellite: drop() (invalidation) must keep the same accounting the
    eviction loop does — bytes released, and a ``dropped`` counter distinct
    from ``evictions``."""
    c = FrameCache(capacity_bytes=1 << 20)
    for i in range(4):
        c.put((0, i), np.full((4, 4, 3), i, np.float32))
    before = c.bytes
    assert before > 0
    n = c.drop(lambda k: k[1] < 2)
    s = c.stats()
    assert n == 2 and s["dropped"] == 2 and s["evictions"] == 0
    assert c.bytes < before and len(c) == 2


def test_cache_entry_capacity_still_enforced():
    c = FrameCache(capacity=2)
    f = np.zeros((2, 2, 3), np.float32)
    c.put(("a",), f.copy())
    c.put(("b",), f.copy())
    c.put(("c",), f.copy())
    assert len(c) == 2 and c.stats()["evictions"] == 1


def test_cache_off_at_zero_budget():
    c = FrameCache(capacity_bytes=0)
    c.put(("a",), np.zeros((2, 2, 3), np.float32))
    assert len(c) == 0 and c.get(("a",)) is None


# ============================================= frame_key resolution satellite
def test_same_pose_different_resolution_never_shares_cache(tmp_path):
    """Regression: frame_key omitted the render resolution, so two servers
    (or any two configs) at the same quantized pose but different output
    sizes shared a key — a cache hit then returned a wrong-size frame (or,
    tile-granular, stitched tiles of the wrong frame). Keys now carry
    (height, width)."""
    g = make_scene(n=256, scale=0.06)
    cam = make_cam(H, W)
    big = _server(g, size=2 * H)
    small = _server(g, size=H)
    small.cache = big.cache  # one shared cache, two resolutions
    f_big = big.submit(cam).result()
    f_small = small.submit(cam).result()
    assert f_big.shape == (2 * H, 2 * W, 3)
    assert f_small.shape == (H, W, 3)
    # the small server really rendered (no cross-resolution key collision)
    assert small.report()["render"]["calls"] == 1
    ref = _server(g, size=H)
    np.testing.assert_array_equal(f_small, ref.submit(cam).result())


# ==================================================== bitwise tile-path suite
def test_strip_render_rows_bitwise_equal_full_frame():
    cfg = GSConfig(img_h=H, img_w=W, k_per_tile=64)
    mesh = make_mesh((1, 1))
    g = make_scene(n=256, scale=0.06)
    cam = make_cam(H, W)
    full = np.asarray(make_batched_eval_render(mesh, cfg)(g, stack_cameras([cam])))[0]
    cam_np = P.Camera(*[np.asarray(x) for x in cam])
    for row in range(H // cfg.tile_h):
        strip = np.asarray(make_tile_row_render(mesh, cfg, row=row)(g, cam_np))
        np.testing.assert_array_equal(strip, full[row * cfg.tile_h : (row + 1) * cfg.tile_h])


@pytest.mark.parametrize("depth", [1, 2])
def test_tile_server_bitwise_equals_whole_frame_baseline(depth):
    """THE equivalence suite: the tile-granular server serves bitwise the
    same frames as the whole-frame baseline across LOD levels, timesteps,
    pipeline depths, and cache replays (assembled-from-tiles frames
    included)."""
    g = make_scene(n=300, scale=0.06)
    g2 = g._replace(means=g.means + np.float32(0.15))
    results = {}
    for tiled in (False, True):
        server = _server(
            g, n_levels=2, pipeline_depth=depth, tile_cache=tiled, cache_capacity=64
        )
        server.add_timestep(1, g2)
        clients = make_clients(3, n_views=6, img_h=H, img_w=W, radius_spread=1.0)
        futs = []
        for r in range(3):
            for cl in clients:
                cam = cl.next_camera()
                futs.append(server.submit(cam, timestep=r % 2))
            # a far viewer exercises the coarse LOD level each round
            futs.append(server.submit(make_cam(H, W, dist=40.0 + r), timestep=0))
            server.run()
        # replay one client's orbit: tile path serves assembled cache hits
        replay = make_clients(3, n_views=6, img_h=H, img_w=W, radius_spread=1.0)
        for cl in replay:
            futs.append(server.submit(cl.next_camera(), timestep=0))
        server.run()
        results[tiled] = [f.result() for f in futs]
        rep = server.report()
        assert rep["lod"]["requests_per_level"][1] > 0  # both levels exercised
        if tiled:
            assert rep["cache"]["hits"] >= 3  # the replay hit assembled tiles
    for a, b in zip(results[False], results[True]):
        np.testing.assert_array_equal(a, b)


def test_partial_hit_renders_only_missing_rows():
    server = _server(cache_capacity=64)
    cam = make_cam(H, W)
    first = server.submit(cam).result()
    calls = server.report()["render"]["calls"]
    tiles_y = server.tiles_y
    server.invalidate(0, rows=[0])  # drop one tile row for this timestep
    fut = server.submit(cam)
    frame = fut.result()
    rep = server.report()
    assert rep["tiles"]["partial_hits"] == 1
    assert rep["tiles"]["rows_rendered_partial"] == 1  # only the dropped row
    assert rep["render"]["calls"] == calls  # no full-frame micro-batch ran
    assert rep["tiles"]["renders_per_frame"] < 1.0
    assert not frame.flags.writeable
    np.testing.assert_array_equal(frame, first)  # model unchanged: bitwise
    assert tiles_y > 1  # the test is vacuous on a single-row config


def test_repeated_full_hits_are_zero_copy():
    """The stitched frame is cached alongside its tiles: a repeated full hit
    hands back the SAME read-only buffer, not a fresh assembly."""
    server = _server(cache_capacity=64)
    cam = make_cam(H, W)
    first = server.submit(cam).result()
    assert server.submit(cam).result() is first
    assert server.report()["render"]["calls"] == 1


def test_invalidate_notifies_listeners_and_counts_drops():
    server = _server(cache_capacity=64)
    seen = []
    server.add_invalidation_listener(lambda ts, rows: seen.append((ts, rows)))
    server.submit(make_cam(H, W)).result()
    dropped = server.invalidate(0)
    assert dropped == server.n_tiles + 1  # every tile + the assembled frame
    assert seen == [(0, None)]  # whole-frame drop: rows is None
    assert server.report()["cache"]["tiles"]["dropped"] == dropped
    # a row-granular invalidation reports exactly the dropped row set
    server.submit(make_cam(H, W)).result()
    server.invalidate(0, rows=[0])
    assert seen[-1] == (0, frozenset({0}))


def test_row_invalidate_on_whole_frame_server_fails_loudly():
    """A whole-frame cache has no row-granular entries: silently widening a
    rows= invalidation to the full frame would hide the caller's wrong
    assumption about what stayed cached."""
    server = _server(tile_cache=False, cache_capacity=64)
    server.submit(make_cam(H, W)).result()
    with pytest.raises(ValueError, match="tile_cache"):
        server.invalidate(0, rows=[0])
    with pytest.raises(ValueError, match="not both"):
        server.add_timestep(0, make_scene(n=256, scale=0.06),
                            changed=[1], dirty_rows=[0])
    server.invalidate(0)  # the full drop still works


def _projected_rows(params, idx, cam, *, img_h, tile_h, pad=0.0):
    """Tile rows covered by the given Gaussians' screen footprints."""
    packed = np.asarray(P.project(params, cam))
    my, rad = packed[idx, P.MY], packed[idx, P.RAD]
    live = rad > 0
    rows = set()
    for y, r in zip(my[live], rad[live]):
        lo = int(np.floor((y - r - pad) / tile_h))
        hi = int(np.floor((y + r + pad) / tile_h))
        rows.update(range(max(lo, 0), min(hi, img_h // tile_h - 1) + 1))
    return rows


def test_add_timestep_dirty_rows_rerenders_only_the_update_region():
    """The in situ partial-invalidation path end-to-end: replacing a model
    whose update touches a bounded screen region with ``dirty_rows`` makes
    the next request a partial hit — and the served frame is bitwise the
    full re-render of the NEW model."""
    size = 48  # 3 tile rows: a one-row update leaves 2/3 of the frame cached
    rng = np.random.default_rng(7)
    g = make_scene(n=300, scale=0.05)
    cam = make_cam(size, size)
    # perturb only Gaussians whose projection sits in the upper screen band
    packed = np.asarray(P.project(g, cam))
    changed = np.nonzero((packed[:, P.MY] < 18.0) & (packed[:, P.RAD] > 0))[0]
    assert changed.size > 0
    means2 = np.asarray(g.means).copy()
    means2[changed] += rng.normal(0, 0.02, (changed.size, 3)).astype(np.float32)
    g2 = g._replace(means=means2)

    server = _server(g, size=size, cache_capacity=64)
    old = server.submit(cam).result()
    rows = _projected_rows(g, changed, cam, img_h=size, tile_h=16)
    rows |= _projected_rows(g2, changed, cam, img_h=size, tile_h=16)
    assert len(rows) < server.tiles_y, "update must not cover the whole frame"
    server.add_timestep(0, g2, dirty_rows=rows)
    frame = server.submit(cam).result()
    rep = server.report()
    assert rep["tiles"]["partial_hits"] == 1
    assert rep["tiles"]["rows_rendered_partial"] == len(rows)
    # ground truth: a fresh server fully renders the new model
    ref = _server(g2, size=size).submit(cam).result()
    np.testing.assert_array_equal(frame, ref)
    assert np.abs(frame - old).max() > 0  # the update was actually visible


def test_add_timestep_changed_autocomputes_dirty_rows():
    """The world-space path end-to-end: ``add_timestep(changed=idx)`` needs
    NO caller row math — the server projects the changed slots through the
    cached pose, drops only their rows, and the next request is a partial
    hit serving bitwise the full re-render of the new model. The computed
    rows must be no looser than a (padded) hand-computed footprint."""
    size = 48  # 3 tile rows
    rng = np.random.default_rng(7)
    g = make_scene(n=300, scale=0.05)
    cam = make_cam(size, size)
    packed = np.asarray(P.project(g, cam))
    changed = np.nonzero((packed[:, P.MY] < 18.0) & (packed[:, P.RAD] > 0))[0]
    assert changed.size > 0
    means2 = np.asarray(g.means).copy()
    means2[changed] += rng.normal(0, 0.02, (changed.size, 3)).astype(np.float32)
    g2 = g._replace(means=means2)

    server = _server(g, size=size, cache_capacity=64)
    old = server.submit(cam).result()  # registers the pose + fills the tiles
    hand = _projected_rows(g, changed, cam, img_h=size, tile_h=16, pad=2.0)
    hand |= _projected_rows(g2, changed, cam, img_h=size, tile_h=16, pad=2.0)
    assert len(hand) < server.tiles_y, "update must not cover the whole frame"
    server.add_timestep(0, g2, changed=changed)
    frame = server.submit(cam).result()
    rep = server.report()
    assert rep["tiles"]["partial_hits"] == 1
    assert 0 < rep["tiles"]["rows_rendered_partial"] <= len(hand)
    ref = _server(g2, size=size).submit(cam).result()
    np.testing.assert_array_equal(frame, ref)
    assert np.abs(frame - old).max() > 0


def test_add_timestep_changed_true_diffs_old_vs_new():
    """``changed=True`` makes the server diff the parameters itself; a
    bit-identical re-registration must then drop NOTHING."""
    size = 48
    g = make_scene(n=300, scale=0.05)
    cam = make_cam(size, size)
    server = _server(g, size=size, cache_capacity=64)
    server.submit(cam).result()
    entries = len(server.cache)
    seen = []
    server.add_invalidation_listener(lambda ts, rows: seen.append((ts, rows)))
    server.add_timestep(0, g, changed=True)  # identical params
    assert len(server.cache) == entries and seen == []
    # a real single-slot change drops a strict subset of the rows
    means2 = np.asarray(g.means).copy()
    means2[0] += np.float32(0.01)
    server.add_timestep(0, g._replace(means=means2), changed=True)
    assert len(seen) == 1 and seen[0][1] is not None


def test_changed_with_no_cached_poses_falls_back_to_full_drop():
    size = 48
    g = make_scene(n=300, scale=0.05)
    server = _server(g, size=size, cache_capacity=64)
    seen = []
    server.add_invalidation_listener(lambda ts, rows: seen.append(rows))
    server.add_timestep(0, g._replace(means=np.asarray(g.means) + 0.01),
                        changed=[0, 1])
    assert seen == [None]  # no registered pose: conservative whole drop


def test_world_space_dirty_rows_conservative_property():
    """Satellite: the conservativeness property. Random slot perturbations
    across several cached poses — every pixel that changes between old and
    new renders lies inside the computed dirty row set, and the complement
    rows are bitwise identical between old and new frames."""
    from repro.serve_gs import dirty_rows as footprint_rows

    size = 48
    th = 16
    rng = np.random.default_rng(11)
    g = make_scene(n=300, scale=0.05)
    server = _server(g, size=size, cache_capacity=256, store_frames=True)
    cams = [make_cam(size, size), make_cam(size, size, dist=6.0)]
    olds = [server.submit(c, timestep=0).result() for c in cams]
    for trial in range(3):
        idx = rng.choice(300, size=int(rng.integers(1, 8)), replace=False)
        means2 = np.asarray(g.means).copy()
        means2[idx] += rng.normal(0, 0.06, (idx.size, 3)).astype(np.float32)
        g2 = g._replace(means=means2)
        ts = 10 + trial  # fresh timeline slot: full renders of the new model
        server.add_timestep(ts, g2)
        for cam, old in zip(cams, olds):
            rows = footprint_rows(
                [g, g2], idx, cam, img_h=size, img_w=size, tile_h=th
            )
            new = server.submit(cam, timestep=ts).result()
            pixel_rows = {
                r for r in range(size // th)
                if np.abs(new[r * th:(r + 1) * th].astype(np.float32)
                          - old[r * th:(r + 1) * th]).max() > 0
            }
            assert pixel_rows <= rows, (trial, pixel_rows, rows)
            for r in set(range(size // th)) - rows:
                np.testing.assert_array_equal(
                    new[r * th:(r + 1) * th], old[r * th:(r + 1) * th]
                )


def test_tile_cache_dedup_across_orbit_poses():
    """Background tiles (empty black) recur across orbit poses and must be
    stored once — the mechanism that lets a tile cache hold more poses than
    a whole-frame cache of the same byte budget."""
    size = 64  # 4x4 tile grid: corner tiles are pure background
    server = _server(size=size, cache_capacity=64)
    # far orbit: the scene covers a fraction of the screen, the rest is
    # identical background tiles from every pose
    clients = make_clients(1, n_views=8, img_h=size, img_w=size, base_radius=10.0)
    for _ in range(8):
        server.submit(clients[0].next_camera())
    server.run()
    s = server.report()["cache"]["tiles"]
    assert s["dedup_shared"] > 0
    assert s["bytes"] + s["dedup_bytes_saved"] > s["bytes"]


# ============================================================ foveated LOD
def test_select_level_map_profiles():
    from repro.serve_gs import select_level_map

    server = _server(n_levels=3, size=48)
    pyr, cam = server.pyramid, make_cam(48, 48)
    # no hints: uniform at the coverage level
    uni = select_level_map(pyr, cam, img_w=48, tiles_y=5)
    assert len(set(uni)) == 1 and len(uni) == 5
    base = uni[0]
    n_lvl = len(pyr.levels)
    # gaze: +1 level per row beyond the sharp zone, clamped to the pyramid
    m = select_level_map(pyr, cam, img_w=48, tiles_y=5, gaze_row=0, sharp_rows=1)
    assert m == tuple(min(base + max(r - 1, 0), n_lvl - 1) for r in range(5))
    # generous budget: everything sharp
    assert select_level_map(
        pyr, cam, img_w=48, tiles_y=5, gaze_row=2, budget_rows=5.0
    ) == (base,) * 5
    # starvation budget: the steepest profile, never an error
    tight = select_level_map(
        pyr, cam, img_w=48, tiles_y=5, gaze_row=2, budget_rows=0.0
    )
    assert tight == tuple(min(base + abs(r - 2), n_lvl - 1) for r in range(5))


def test_foveated_frame_assembles_bitwise_from_per_level_tiles():
    """A mixed-level frame must be row-for-row bitwise identical to the
    uniform render of each row's assigned level — and reuse the uniform
    frames' cached tiles (only the coarse rows strip-render)."""
    size = 48  # 3 tile rows
    th = 16
    g = make_scene(n=300, scale=0.06)
    server = _server(g, size=size, n_levels=2, cache_capacity=256)
    cam = make_cam(size, size)
    uniform = server.submit(cam).result()  # level 0, fills its tiles
    calls = server.report()["render"]["calls"]

    fov = server.submit(cam, gaze=(0.5, 0.0)).result()  # gaze at the top
    rep = server.report()
    assert rep["lod"]["foveated_requests"] == 1
    # sharp zone reused the uniform level-0 tiles: only coarse rows rendered
    assert rep["render"]["calls"] == calls
    assert rep["tiles"]["partial_hits"] == 1
    assert 0 < rep["tiles"]["rows_rendered_partial"] < server.tiles_y
    # per-row ground truth from the engine's own level renders
    entry = server._timeline[0]
    from repro.serve_gs import stack_cameras as _stack
    levels = {
        lvl: np.asarray(server._level_render[lvl](entry.level_params[lvl], _stack([cam])))[0]
        for lvl in range(len(entry.level_params))
    }
    np.testing.assert_array_equal(levels[0], uniform)
    expected = (0, 0, 1)  # gaze row 0, sharp_rows=1 -> rows 0,1 sharp, row 2 coarse
    for r, lvl in enumerate(expected):
        np.testing.assert_array_equal(
            fov[r * th:(r + 1) * th], levels[lvl][r * th:(r + 1) * th]
        )
    assert np.abs(fov.astype(np.float32) - uniform).max() > 0  # really mixed
    # the stitched mixed frame is itself cached: replay is a zero-copy hit
    assert server.submit(cam, gaze=(0.5, 0.0)).result() is fov
    # per-level row accounting reached the report
    assert rep["lod"]["rows_per_level"][0] >= server.tiles_y + 2
    assert rep["lod"]["rows_per_level"][1] >= 1


def test_gaze_hint_ignored_on_whole_frame_server():
    server = _server(tile_cache=False, cache_capacity=64)
    cam = make_cam(H, W)
    a = server.submit(cam).result()
    b = server.submit(cam, gaze=(0.5, 0.0), budget_ms=1.0).result()
    np.testing.assert_array_equal(a, b)


def test_frame_key_is_prefix_of_tile_keys():
    cam = make_cam(H, W)
    k = frame_key(cam, 0, height=H, width=W)
    tk = tile_key(k, 3)
    assert tk[: len(k)] == k and tk[-1] == 3 and tk[0] == 0
