"""Hardware autotune: probe the gather-mode and sampling-RNG space on the
current accelerator and persist the winners as library defaults.

Run once per hardware generation:

    python benchmarks/autotune.py [--fanout 15 10 5 --batch 512]

Writes ``.quiver_tpu_tuned.json`` at the repo root;
``quiver_tpu.config.get_config()`` picks it up automatically, so samplers
constructed with ``gather_mode="auto"`` / ``sample_rng="auto"`` use the
measured winners.

Every probe runs in THIS process, on one reduced graph uploaded once
(``bench.probe_sampler``): the chip belongs to one process at a time, so
a probing child would find it held.  A mode the compiler refuses is
printed and left out.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fanout", type=int, nargs="+", default=[15, 10, 5])
    ap.add_argument("--batch", type=int, default=512)
    args = ap.parse_args()

    import jax

    from bench import build_graph, probe_sampler
    from quiver_tpu import CSRTopo
    from quiver_tpu.utils import compile_cache

    compile_cache.enable()
    # the ranking is taken on a reduced graph; at products size: not
    # measured
    indptr, indices = build_graph(200_000, 4_000_000)
    topo = CSRTopo(indptr=indptr, indices=indices)

    def probe(gm, srng="auto"):
        tag = f"{gm}" + (f"+{srng}" if srng != "auto" else "")
        try:
            ms = probe_sampler(topo, gm, args.fanout, args.batch,
                               sample_rng=srng)
        except Exception as e:  # noqa: BLE001 — a refused mode is a result
            print(f"{tag}: refused ({type(e).__name__}: {str(e)[:300]})")
            return None
        print(f"{tag}: {ms:.1f} ms/batch")
        return ms

    from bench import GATHER_MODES_VERSION, PROBE_MODES, _tuned_path

    tuned_path = _tuned_path()

    results = {gm: ms for gm in PROBE_MODES
               if (ms := probe(gm)) is not None}
    if not results:
        print("no mode succeeded; nothing written")
        return
    best = min(results, key=results.get)

    # A/B the uniform source under the winning gather mode (key-based
    # jax.random.uniform vs counter-hash)
    rng_results = {srng: ms for srng in ("key", "hash")
                   if (ms := probe(best, srng)) is not None}

    payload = {
        "gather_mode": best,
        "device": str(jax.devices()[0]),
        # without this tag bench.pick_gather_mode distrusts the file and
        # re-probes every session (version gate on the mode set)
        "modes_version": GATHER_MODES_VERSION,
        "probe_ms": {k: round(v, 2) for k, v in results.items()},
    }
    if rng_results:
        payload["sample_rng"] = min(rng_results, key=rng_results.get)
        payload["rng_probe_ms"] = {
            k: round(v, 2) for k, v in rng_results.items()
        }
    # merge (bench.merge_tuned) so a dedup winner persisted by the e2e
    # A/B survives an autotune re-run
    from bench import merge_tuned

    written = merge_tuned(payload, jax.default_backend(), tuned_path)
    print(f"tuned defaults -> {tuned_path}: {written}")


if __name__ == "__main__":
    main()
