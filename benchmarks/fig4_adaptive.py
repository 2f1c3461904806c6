"""Fig. 4 analogue: per-run runtimes vs target across the adaptive campaign
(with anomalous phases marked) — ASCII rendering + summary stats."""
from __future__ import annotations

from typing import Dict

import numpy as np

from benchmarks.experiment import get_or_run
from repro.compile_cache import enable_compile_cache


def summarize(job: str, n_adaptive: int = 55, seed: int = 0) -> Dict:
    out = {}
    for method in ("enel", "ellis"):
        res = get_or_run(job, method, n_adaptive=n_adaptive, seed=seed)
        runs = res["runs"]
        normal = [r for r in runs if not r["anomalous"]]
        anom = [r for r in runs if r["anomalous"]]
        halves = np.array_split([r["violation"] for r in runs], 2)
        out[method] = {
            "target": res["target"],
            "viol_normal_mean": float(np.mean([r["violation"] for r in normal])),
            "viol_anomalous_mean": float(np.mean([r["violation"] for r in anom]))
            if anom else 0.0,
            "viol_first_half": float(np.mean(halves[0])),
            "viol_second_half": float(np.mean(halves[1])),
            "failures_total": int(sum(r["n_failures"] for r in runs)),
        }
    return out


def render_ascii(job: str, n_adaptive: int = 55, seed: int = 0) -> str:
    res = get_or_run(job, "enel", n_adaptive=n_adaptive, seed=seed)
    target = res["target"]
    lines = [f"{job}: runtime vs target={target:.0f}s "
             f"(# anomalous, . normal; bar = overshoot)"]
    for r in res["runs"]:
        over = max(0.0, r["runtime"] - target)
        bar = "#" if r["anomalous"] else "."
        lines.append(f"run {r['run_idx']:3d} {bar} "
                     f"{r['runtime']:7.0f}s |{'=' * min(60, int(over / 5))}")
    return "\n".join(lines)


def main(n_adaptive: int = 55):
    enable_compile_cache()
    for job in ("lr", "mpc", "kmeans", "gbt"):
        s = summarize(job, n_adaptive)
        for method, v in s.items():
            print(f"fig4,{job},{method},viol_1st_half={v['viol_first_half']:.1f}s,"
                  f"viol_2nd_half={v['viol_second_half']:.1f}s,"
                  f"viol_anomalous={v['viol_anomalous_mean']:.1f}s")
    return True


if __name__ == "__main__":
    main()
