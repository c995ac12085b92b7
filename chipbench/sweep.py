"""Find a deployment's knee: the highest open-loop rate whose backlog
does not grow over the window.

    python3 chipbench/sweep.py --config <name> --traffic <mix> [--traffic <mix> ...]
        --rates 1000,2000,... [--seconds 5] [--seed 7]

Builds the configuration once, warms it as a run does, then drives each
mix's queries through the front door at each rate in turn, for
``--seconds`` each. Per rate it prints one JSON line: reads, p50 and p99
(ms), and the median latency of the last quarter of the reads against
the first quarter's. A backlog that grows shows as a last quarter far
slower than the first, and as a p99 near the window's length.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
for p in (str(BENCH_DIR), str(BENCH_DIR.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from harness import client, deploy, spec, tpch, traffic, warmup  # noqa: E402


def require_tpu() -> None:
    import jax

    if jax.devices()[0].platform != "tpu":
        sys.exit("sweep: no TPU")


def main(argv=None, rows: int | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", action="append", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    import jax

    require_tpu()
    jax.config.update("jax_compilation_cache_dir", str(BENCH_DIR.parent / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cfg = spec.load_json(spec.config_file(args.config))
    n_rows, kc, vc = deploy.make_data(cfg, args.seed, rows)
    dom = tpch.domains(n_rows)
    eng, cf = deploy.build_engine(cfg, kc, vc, dom)
    max_batch = int(cfg["frontdoor"]["max_batch"])
    timeout_s = cfg["guarantees"]["read_timeout_ms"] / 1000.0
    for name in args.traffic:
        templates = spec.load_json(spec.traffic_file(name))["templates"]
        warmup.warm(eng, cf, templates, dom, max_batch, timeout_s, args.seed)
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            due = traffic.arrivals(rate, args.seconds, [args.seed, 10 + k])
            qs = traffic.to_queries(traffic.queries(templates, dom, len(due), [args.seed, 20 + k]))
            fd = client.make_frontdoor(eng, max_batch)
            client.settle()
            with client.GcPauses() as gcp:
                win = client.open_loop(fd, cf, qs, due, max_batch=max_batch, timeout_s=timeout_s)
            lat = win.latency_s * 1e3
            q = max(1, len(lat) // 4)
            print(json.dumps({
                "config": args.config, "traffic": name, "rate_per_s": rate,
                "reads": len(lat), "ok": int(np.sum(win.status == client.OK)),
                "batches": win.batches, "reads_per_batch": len(lat) / win.batches,
                "p50_ms": float(np.nanpercentile(lat, 50)),
                "p99_ms": float(np.nanpercentile(lat, 99)),
                "first_quarter_p50_ms": float(np.nanmedian(lat[:q])),
                "last_quarter_p50_ms": float(np.nanmedian(lat[-q:])),
                "window_s": win.length_s, "gc": gcp.summary(),
            }), flush=True)


if __name__ == "__main__":
    main()
