"""Table III analogue: evolution of CVC/CVS over adaptive-run windows,
Enel vs Ellis, per job."""
from __future__ import annotations

from typing import Dict, List

from benchmarks.experiment import campaign_window_stats, get_or_run
from repro.compile_cache import enable_compile_cache

JOBS_ORDER = ["lr", "mpc", "kmeans", "gbt"]


def run(jobs: List[str] = JOBS_ORDER, methods=("enel", "ellis"),
        n_adaptive: int = 55, seed: int = 0) -> Dict:
    table = {}
    for job in jobs:
        for method in methods:
            res = get_or_run(job, method, n_adaptive=n_adaptive, seed=seed)
            table[(job, method)] = campaign_window_stats(res)
    return table


def render(table: Dict) -> str:
    lines = ["| job | method | " + " | ".join(
        f"W{i+1} cvc x̄/x̃ · cvs x̄/x̃ (m)" for i in range(5)) + " |",
        "|---|---|" + "---|" * 5]
    for (job, method), ws in sorted(table.items()):
        cells = [f"{w['cvc_mean']:.2f}/{w['cvc_median']:.2f} · "
                 f"{w['cvs_mean']:.2f}/{w['cvs_median']:.2f}" for w in ws]
        lines.append(f"| {job} | {method} | " + " | ".join(cells) + " |")
    return "\n".join(lines)


def main(n_adaptive: int = 55):
    enable_compile_cache()
    table = run(n_adaptive=n_adaptive)
    print(render(table))
    return table


if __name__ == "__main__":
    main()
