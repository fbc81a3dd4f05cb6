"""One cell of the benchmark, once.

``python3 cellbench/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>``: load the cell's configuration and traffic files, make the
data from the seed, build the program, warm it up (all of that is
``setup_s``), measure for ``--seconds``, compare what the timed path
produced with the plain reference, and print one JSON line.

Everything that tells one deployment from another is a file that this one
finds by a name the cell's data gives (``load_named``): ``BENCHMARK.json``
names the cell's configuration file and its traffic mix
(``traffic/<mix>.json``) and the per-layer metrics (``metrics/<name>.py``);
the configuration names its program (``programs/<name>.py``), its plain
reference with the seeded data (``references/<name>.py``) and its work
model (``work/<name>.py``); the traffic file names its kind
(``kinds/<kind>.py``).  PERF.md section 4 says what each has to expose.

It needs a TPU and exits 2 without one, before building anything.
``--rehearse`` runs the same code at a toy size on whatever JAX finds, for
the tests: it prints no metric at all, only ``correct`` and what was
compared, under ``"rehearsal": true``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from concurrent.futures import Future  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def find_file(folder, name, ext):
    """``cellbench/<folder>/<name><ext>``; a name with no file exits with
    the names that have one."""
    path = os.path.join(HERE, folder, name + ext)
    if not os.path.isfile(path):
        there = sorted(f[:-len(ext)] for f in os.listdir(
            os.path.join(HERE, folder)) if f.endswith(ext))
        raise SystemExit(f"no cellbench/{folder}/{name}{ext}; there are "
                         f"{there}")
    return path


def load_named(folder, name):
    """The module ``cellbench/<folder>/<name>.py``: a program, a kind, a
    reference, a work model or a per-layer reader.  Once per process."""
    modname = "cb_" + folder + "_" + re.sub(r"\W", "_", name)
    if modname not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            modname, find_file(folder, name, ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[modname] = mod
    return sys.modules[modname]


def find_cell(name):
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; there "
                         f"are {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(os.path.join(ROOT, conf["file"]))
    traffic = load_json(find_file("traffic", cell["traffic"], ".json"))
    return bench, cell, cfg, traffic


def limits_of(cfg, cell):
    """The limits of a pair sit with the configuration, by the mix's name."""
    return cfg.get("limits", {}).get(cell["traffic"], {})


def compare(numbers, limits):
    """Each number beside its limit; ``correct`` needs every limit to be
    there and kept."""
    compared, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name)
        kept = value is not None and value == value and value <= limit
        compared[name] = {"value": value, "limit": limit}
        ok = ok and kept
    return compared, ok and bool(limits)


def read_layer_metrics(bench, cell, ctx):
    """One reader per per-layer metric, found by the metric's name."""
    out = {}
    for m in bench["per_layer"]:
        if cell["name"] not in m.get("workloads", [cell["name"]]):
            continue
        value = load_named("metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def rehearsal_size(cfg, traffic):
    """Cut a cell to the toy size its files give under ``rehearsal``."""
    cfg.update(cfg["rehearsal"])
    traffic.update(traffic["rehearsal"])


def parts_of(cfg, traffic):
    """The four files that make the cell's deployment, by the names its
    data gives."""
    return {"program": load_named("programs", cfg["program"]),
            "reference": load_named("references", cfg["reference"]),
            "work": load_named("work", cfg["work"]),
            "kind": load_named("kinds", traffic["kind"])}


def make_data(cfg, seed):
    """The cell's data, all from the seed, as its reference makes it."""
    return load_named("references", cfg["reference"]).make_data(cfg, seed)


def run_cell(cell, cfg, traffic, seed, seconds, trace, control=False,
             fault=None, keep_trace=None, data=None):
    """Everything between the arguments and the result line, as a dict of
    its pieces: the end-to-end metrics, the facts and the reduced trace for
    the per-layer readers, the device, the numbers compared, and the
    check's second half (``numbers_fn`` over ``replayed``) for the proof
    script and the tests.  ``data``: a future of ``make_data``'s result."""
    import jax

    import cells
    import compile_cache
    import trace_reduce
    from watch import CompileWatch

    found = jax.devices()
    devices = found[:cell["chips"]]
    dev = devices[0]
    watch = CompileWatch()
    cache = compile_cache.cache_dir()
    log(f"device {dev.platform} {dev.device_kind} x {len(found)}, the cell "
        f"takes {len(devices)}; compile cache at {cache}")
    parts = parts_of(cfg, traffic)
    ref = parts["reference"]
    t = time.perf_counter()
    data = ref.make_data(cfg, seed) if data is None else data.result()
    log(f"data ready after {time.perf_counter() - t:.1f} s more")
    t = time.perf_counter()
    prog = parts["program"].Program(cfg, data, devices, control=control,
                                    fault=fault)
    log(f"program built in {time.perf_counter() - t:.1f} s: "
        f"{prog.resolved()}")
    trace_dir = os.path.join(HERE, ".trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    tracer = cells.Tracer(trace, trace_dir,
                          min(seconds, traffic["traced_seconds"]))
    end_to_end, facts, (replay, numbers) = parts["kind"].run(
        prog, ref, cfg, traffic, data, seed, seconds, tracer, watch)
    end_to_end["setup_s"] = facts["t_setup_end"] - T_START
    log(f"window closed: {watch.compiles} programs built in all, "
        f"{watch.compile_s:.1f} s in the compiler, cache hits "
        f"{watch.cache_hits} misses {watch.cache_misses}; "
        f"{facts['window_compiles']} inside the window")

    # the peak on the fullest of the cell's devices, and each beside it
    peak_bytes = []
    for d in devices:
        stats = d.memory_stats() or {}
        log(f"memory_stats of device {d.id}: {stats}")
        peak_bytes.append(stats.get("peak_bytes_in_use"))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(found), "chips": len(devices),
              "memory_peak_bytes": max(
                  (p for p in peak_bytes if p is not None), default=None),
              "memory_peak_bytes_per_device": peak_bytes}
    red = None
    if trace:
        red = trace_reduce.reduce_trace(trace_dir)
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(trace_reduce.newest_xplane(trace_dir), keep_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            device["busy_s_per_device"] = red["busy_s_per_device"]
            device["idle_gaps_of_device"] = red["idle_gaps_device"]
            log("programs launched in the traced window: " + ", ".join(
                f"{name} x {fam['launches']} ({fam['seconds']:.3f} s)"
                for name, fam in sorted(red["modules"].items(),
                                        key=lambda kv: -kv[1]["seconds"])))
            log("host spans in the traced window: " + (", ".join(
                f"{name} x {sp['count']} ({sp['seconds']:.3f} s, "
                f"{sp['idle_overlap_s']:.3f} s of it device idle)"
                for name, sp in sorted(red["host_spans"].items())) or "none"))

    t = time.perf_counter()
    replayed = replay()
    got = numbers(replayed, cfg["precision"]["matmul"])
    log(f"compared with the reference in {time.perf_counter() - t:.1f} s")
    return {"end_to_end": end_to_end, "facts": facts, "device": device,
            "trace": red, "numbers": got, "replayed": replayed,
            "numbers_fn": numbers}


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the .xplane.pb here, to look at by hand")
    args = ap.parse_args(argv)

    bench, cell, cfg, traffic = find_cell(args.workload)
    if args.rehearse:
        rehearsal_size(cfg, traffic)
    parts = parts_of(cfg, traffic)    # a name with no file exits here

    # the data is made on the host while JAX reaches the chip; a daemon
    # thread, so that a refusal below exits at once
    data = Future()

    def make():
        try:
            data.set_result(make_data(cfg, args.seed))
        except BaseException as e:  # handed to the thread that waits
            data.set_exception(e)

    threading.Thread(target=make, name="cb-data", daemon=True).start()

    import jax

    devices = jax.devices()
    if not args.rehearse and devices[0].platform != "tpu":
        log(f"cellbench: needs a TPU, JAX found {devices[0].platform!r}; "
            f"nothing was built and there is no result")
        return 2
    if len(devices) < cell["chips"]:
        log(f"cellbench: {cell['name']} needs {cell['chips']} chips, JAX "
            f"found {len(devices)}")
        return 2

    import peaks
    import trace_reduce

    out = run_cell(cell, cfg, traffic, args.seed, args.seconds, args.trace,
                   keep_trace=args.keep_trace, data=data)
    compared, correct = compare(out["numbers"], limits_of(cfg, cell))
    facts = out["facts"]
    if args.rehearse:
        result = {"rehearsal": True, "correct": correct,
                  "attempted": facts["attempted"],
                  "failed": facts["failed"], "compared": compared}
    else:
        if args.trace:
            ctx = {"facts": facts, "trace": out["trace"], "cfg": cfg,
                   "traffic": traffic, "work": parts["work"],
                   "peak": peaks.peaks(out["device"]["kind"]),
                   "end_to_end": out["end_to_end"]}
            metrics = read_layer_metrics(bench, cell, ctx)
        else:
            metrics = {m["name"]: {"value": out["end_to_end"][m["name"]],
                                   "unit": m["unit"]}
                       for m in bench["end_to_end"]
                       if cell["name"] in m.get("workloads", [cell["name"]])}
        result = {"correct": correct, "attempted": facts["attempted"],
                  "failed": facts["failed"], "metrics": metrics,
                  "device": out["device"]}
        if args.trace and out["trace"] is not None:
            result["breakdown"] = trace_reduce.breakdown(out["trace"])
        result["compared"] = compared
    for name, c in compared.items():
        log(f"compared {name}: {c['value']} (limit {c['limit']})")
    log(f"correct: {correct}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
