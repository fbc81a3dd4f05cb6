"""The kind ``train`` (a traffic file names it under ``"kind"``): a fused
training step driven back to back in epoch order.  Every parameter comes
from the traffic file.

``run`` returns ``(end_to_end, facts, check)``: the end-to-end metrics off
the host's clock, the counts and clock readings the per-layer readers use,
and a pair ``(replay, numbers)`` for once the window has closed and the
peak has been read: ``replay()`` reads the sampler's draws back and frees
the program's tables, ``numbers(replayed, matmul)`` compares what the timed
path produced with the reference.

Of the program it calls ``fused_train_step``, ``make_key``, ``step_keys``,
``first_gradient``, ``replay_sample`` and ``free``; of the reference
``check_sample``, ``train_follow`` and ``leaf_norm_gap``.
"""

import gc
import queue
import threading
import time

import numpy as np

import datagen
from cells import settle, span

# the faults a training step can have, planted in the program
# (``Program(fault=...)``) or in the reference put in its place
# (``numbers(..., fault=...)``): cellbench/tests/test_faults.py, prove.py
FAULTS = ("stale_state", "half_batch")


def run(prog, ref, cfg, traffic, data, seed, seconds, tracer, watch):
    import jax
    import jax.numpy as jnp

    B = cfg["batch"]
    per_epoch = cfg["train_nodes"] // B
    # enough epochs for any step time the window could see
    epochs = max(int(seconds * traffic["max_steps_per_s"]) // per_epoch + 2,
                 2)
    order = datagen.train_order(cfg, seed, epochs)
    host_labels = data["labels"]
    base_key = prog.make_key(seed % (2 ** 31 - 1))
    ones = jnp.ones((B,), bool)
    state, step = prog.fused_train_step()
    params0 = jax.tree_util.tree_map(np.asarray, state.params)

    def feed(i):
        s = order[i]
        return (jnp.asarray(s), jnp.asarray(host_labels[s]),
                jax.random.fold_in(base_key, i))

    # -- the first steps, through the window's own call and feed; the
    #    reference follows them once the window has closed
    n_check = traffic["checked_steps"]
    seen = {"losses": [], "keys": []}
    for i in range(n_check):
        seeds_d, labels_d, key = feed(i)
        state, loss = step(state, seeds_d, labels_d, ones, key)
        seen["losses"].append(float(loss))
        seen["keys"].append(key)
        if i == 0:
            seen["grad"] = prog.first_gradient(state)
    seen["params"] = jax.tree_util.tree_map(np.asarray, state.params)

    # -- the loader thread: seeds and labels one batch ahead
    feedq = queue.Queue(maxsize=traffic["prefetch"])
    stop = threading.Event()

    def loader():
        i = n_check
        while not stop.is_set():
            with span("generate"):
                item = feed(i)
            while not stop.is_set():
                try:
                    feedq.put(item, timeout=0.05)
                    break
                except queue.Full:
                    pass
            i += 1

    th = threading.Thread(target=loader, name="cb-loader", daemon=True)
    th.start()
    # warm: a few steps off the loader, so that the queue is full and
    # nothing in the window is a first call
    for _ in range(traffic["warm_steps"]):
        seeds_d, labels_d, key = feedq.get()
        state, loss = step(state, seeds_d, labels_d, ones, key)
    jax.block_until_ready(loss)

    compiles0 = watch.compiles
    inflight, steps, traced_steps = [], 0, None
    settle()
    tracer.start()
    t0 = time.perf_counter()
    while True:
        with span("dispatch"):
            seeds_d, labels_d, key = feedq.get()
            state, loss = step(state, seeds_d, labels_d, ones, key)
        inflight.append(loss)
        steps += 1
        if len(inflight) > traffic["steps_in_flight"]:
            with span("wait_result"):
                jax.block_until_ready(inflight.pop(0))
        if tracer.due():
            jax.block_until_ready(loss)
            tracer.stop()
            traced_steps = steps
        if time.perf_counter() - t0 >= seconds:
            break
    with span("wait_result"):
        jax.block_until_ready(state.params)
    elapsed = time.perf_counter() - t0
    gc.unfreeze()
    if tracer.on and tracer.t1 is None:
        tracer.stop()
        traced_steps = steps
    stop.set()
    th.join(timeout=10)
    tracer.join()
    last_loss = float(loss)

    end_to_end = {"train_seeds_per_s": steps * B / elapsed}
    facts = {"kind": "train", "steps": steps, "elapsed_s": elapsed,
             "window_compiles": watch.compiles - compiles0,
             "traced_steps": traced_steps, "batch": B,
             "traced_s": (tracer.t1 - tracer.t0) if tracer.on else None,
             "t_setup_end": t0, "attempted": steps,
             "failed": 0 if np.isfinite(last_loss) else steps}

    def replay():
        """Read the draws of the first steps back and hold them to the
        CSR; then the program's tables leave the chip."""
        batches = []
        bad_total = 0
        for i in range(n_check):
            ks, kd = prog.step_keys(seen["keys"][i])
            n_id, n_mask, layers = prog.replay_sample(order[i], ks)
            bad, _ = ref.check_sample(
                data["indptr"], data["indices"], cfg["fanout"], order[i],
                n_id, n_mask, layers)
            bad_total += sum(bad.values())
            batches.append({
                "rows": table_rows(data, n_id), "layers": layers,
                "labels": host_labels[order[i]], "drop_key": kd})
        prog.free()
        return batches, bad_total

    def numbers(replayed, matmul, fault=None, stand_in=None):
        """The first steps against the reference.  ``fault`` or
        ``stand_in`` (a lower precision) put the reference, so altered, in
        the program's place: the readings a limit's upper end is set
        from."""
        batches, bad_total = replayed
        tm = jax.tree_util.tree_map
        losses, grad, params = ref.train_follow(
            data["params"], batches, cfg, matmul)
        seen_ = seen
        if fault is not None or stand_in is not None:
            a, b, c = ref.train_follow(
                data["params"], batches, cfg, stand_in or matmul, fault)
            seen_ = {"losses": a, "grad": b, "params": c}
        return {
            "sample_breaches": float(bad_total),
            "loss_gap": max(abs(a - b) / abs(b)
                            for a, b in zip(seen_["losses"], losses)),
            "grad_gap": ref.leaf_norm_gap(seen_["grad"], grad),
            "delta_gap": ref.leaf_norm_gap(
                tm(lambda a, b: a - b, seen_["params"], params0),
                tm(lambda a, b: a - b, params, data["params"]),
                skip_below=grad),
        }

    return end_to_end, facts, (replay, numbers)


def table_rows(data, n_id):
    """Rows of the host table as the configuration stores them."""
    return data["features"][n_id].astype(np.float32)

