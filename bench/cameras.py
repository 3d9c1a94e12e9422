"""Camera poses of the benchmark's traffic, kept with the benchmark.

The orbit follows the program's structured capture rig (spiral orbit around
the origin, elevation ``elev_max_deg * sin(elev_cycles * azimuth)``, +z up,
40 degree field of view); the look-at convention is the 3D-GS one (camera +z
forward, +y down). Copied here so that a change to the program's rig does not
move the yardstick. A camera is a dict of numpy float32 arrays: ``viewmat``
(4, 4) world to camera, and scalars ``fx``, ``fy``, ``cx``, ``cy``.
"""
from __future__ import annotations

import numpy as np


def look_at(eye, target=(0.0, 0.0, 0.0), up=(0.0, 0.0, 1.0)) -> np.ndarray:
    """World-to-camera matrix of a camera at ``eye`` looking at ``target``."""
    eye = np.asarray(eye, np.float64)
    fwd = np.asarray(target, np.float64) - eye
    fwd /= np.linalg.norm(fwd)
    right = np.cross(fwd, up)
    right /= np.linalg.norm(right)
    down = np.cross(fwd, right)
    rot = np.stack([right, down, fwd])
    m = np.eye(4)
    m[:3, :3] = rot
    m[:3, 3] = -rot @ eye
    return m.astype(np.float32)


def camera(eye, res: int, fov_deg: float) -> dict:
    f = 0.5 * res / np.tan(np.deg2rad(fov_deg) / 2)
    return {"viewmat": look_at(eye), "fx": np.float32(f), "fy": np.float32(f),
            "cx": np.float32(res / 2), "cy": np.float32(res / 2)}


def spherical(azimuth: float, elevation: float, radius: float) -> np.ndarray:
    return radius * np.array([np.cos(elevation) * np.cos(azimuth),
                              np.cos(elevation) * np.sin(azimuth), np.sin(elevation)])


def orbit(config: dict, res: int) -> list[dict]:
    """The configuration's ``n_views`` capture views at ``res`` pixels."""
    o = config["orbit"]
    n = config["n_views"]
    az = np.linspace(0, 2 * np.pi, n, endpoint=False)
    elev = np.deg2rad(o["elev_max_deg"]) * np.sin(o["elev_cycles"] * az)
    return [camera(spherical(a, e, config["orbit_radius"]), res, o["fov_deg"])
            for a, e in zip(az, elev)]


def to_program(cam: dict):
    """One camera as the program's ``Camera`` (numpy leaves)."""
    from repro.core.projection import Camera

    return Camera(cam["viewmat"], cam["fx"], cam["fy"], cam["cx"], cam["cy"])


def stack_to_program(cams: list[dict]):
    """A batch of cameras as one program ``Camera`` with a leading axis."""
    from repro.core.projection import Camera

    return Camera(*[np.stack([c[k] for c in cams]) for k in ("viewmat", "fx", "fy", "cx", "cy")])
