"""The plain reference of a schedule, which ``correct`` compares the
program's ``schedule`` answers with. Numpy only; imports nothing of the
program.

It follows the rule that the program's scheduler documents
(``core/scheduler.schedule``), for devices that each offer a DVFS grid,
one queue per device:

* Pricing: a kernel's time at relative clock ``f`` is its nominal time
  over ``f``, its power the nominal power times ``f**3``.
* Placement: kernels in order of their fastest time over every device and
  clock, longest first (``np.argsort`` of its negation). Each kernel is
  tried on every queue, in device order; for the objective ``energy``
  only at the device's fastest clock, at the cost of its least energy
  (power times time) over the device's grid, else at every clock of the
  grid, at the cost of its completion (``makespan``), its energy
  (``energy``) or completion times energy (``edp``). An option is
  feasible when its completion plus a reservation for the kernels still
  to place (the sum of their fastest times over the number of queues)
  meets the deadline; without a deadline every option is. The least
  (cost, completion) among feasible options wins, and where none is
  feasible the least completion; ties keep the first option tried.
* Downshift (objective ``energy``): per queue, in the order queues were
  first used, repeatedly step one kernel one clock down its grid, the
  step that saves most energy per microsecond added (ties: the longer
  kernel, then the one placed first), among steps that save energy and
  fit the queue's slack to the deadline, until none is left. Then each
  queue's starts are its kernels' times summed in order of placement.
* Totals: the makespan is the largest queue's total time, the energy
  the sum of power times time over all assignments, in joules.
"""
from __future__ import annotations

import numpy as np

OBJECTIVES = ("makespan", "energy", "edp")


def operating_points(t_us: np.ndarray, p_w: np.ndarray, grids: list):
    """Nominal (kernels, devices) times and powers -> (kernels, devices,
    points) tensors over each device's grid, ``inf`` past a device's
    last point."""
    n, d = t_us.shape
    width = max(len(g) for g in grids)
    T = np.full((n, d, width), np.inf)
    P = np.full((n, d, width), np.inf)
    for j, grid in enumerate(grids):
        for g, f in enumerate(grid):
            T[:, j, g] = t_us[:, j] / f
            P[:, j, g] = p_w[:, j] * f ** 3
    return T, P


def schedule(t_us: np.ndarray, p_w: np.ndarray, grids: list,
             objective: str, deadline_us: float | None = None) -> dict:
    """The schedule of the kernels whose nominal time (µs) and power (W)
    on each device are ``t_us`` and ``p_w`` (kernels, devices).

    Returns ``assignments``, one dict per kernel in order of placement
    (``kernel``, ``device``: the device's index, ``freq``, ``t_us``,
    ``power_w``, ``start_us``), ``makespan_us``, ``energy_j`` and
    ``meets_deadline`` (None without a deadline)."""
    if objective not in OBJECTIVES:
        raise ValueError(f"unknown objective {objective!r}")
    T, P = operating_points(t_us, p_w, grids)
    n_dev = len(grids)
    fastest = T.min(axis=(1, 2))
    least_energy = (P * T).min(axis=2)
    todo = float(fastest.sum())
    total = np.zeros(n_dev)
    placed = []
    for k in np.argsort(-fastest):
        todo -= float(fastest[k])
        reserve = 0.0 if deadline_us is None else todo / n_dev
        best = None
        for j, grid in enumerate(grids):
            points = ([int(np.argmax(grid))] if objective == "energy"
                      else range(len(grid)))
            for g in points:
                t, p = T[k, j, g], P[k, j, g]
                end = total[j] + t
                if objective == "makespan":
                    cost = end
                elif objective == "energy":
                    cost = least_energy[k, j]
                else:
                    cost = end * p * t
                if deadline_us is None or end + reserve <= deadline_us:
                    rank = (0, cost, end)
                else:
                    rank = (1, end, end)
                if best is None or rank < best[0]:
                    best = (rank, j, g)
        _, j, g = best
        placed.append({"kernel": int(k), "device": j, "g": g,
                       "freq": float(grids[j][g]), "t_us": float(T[k, j, g]),
                       "power_w": float(P[k, j, g]),
                       "start_us": float(total[j])})
        total[j] += T[k, j, g]
    if objective == "energy":
        _downshift(placed, T, P, grids, total, deadline_us)
    return {"assignments": placed,
            "makespan_us": float(total.max()),
            "energy_j": sum(a["power_w"] * a["t_us"] for a in placed) * 1e-6,
            "meets_deadline": (None if deadline_us is None
                               else bool(total.max() <= deadline_us))}


def _downshift(placed, T, P, grids, total, deadline_us) -> None:
    queues: dict[int, list[int]] = {}
    for i, a in enumerate(placed):
        queues.setdefault(a["device"], []).append(i)
    for j, members in queues.items():
        grid = grids[j]
        while True:
            slack = np.inf if deadline_us is None else deadline_us - total[j]
            best = None
            for i in members:
                a = placed[i]
                slower = [g for g, f in enumerate(grid) if f < a["freq"]]
                if not slower:
                    continue
                g = max(slower, key=lambda s: grid[s])
                k = a["kernel"]
                dt = T[k, j, g] - a["t_us"]
                de = P[k, j, g] * T[k, j, g] - a["power_w"] * a["t_us"]
                if de >= 0 or dt > slack:
                    continue
                rank = (de / max(dt, 1e-12), -a["t_us"], i)
                if best is None or rank < best[0]:
                    best = (rank, i, g)
            if best is None:
                break
            _, i, g = best
            a = placed[i]
            k = a["kernel"]
            total[j] += T[k, j, g] - a["t_us"]
            a.update(g=g, freq=float(grid[g]), t_us=float(T[k, j, g]),
                     power_w=float(P[k, j, g]))
        start = 0.0
        for i in members:
            placed[i]["start_us"] = start
            start += placed[i]["t_us"]
