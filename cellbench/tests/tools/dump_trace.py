import sys, jax, collections
pd = jax.profiler.ProfileData.from_file(sys.argv[1])
for pl in pd.planes:
    lines = list(pl.lines)
    print("PLANE", repr(pl.name), len(lines))
    for ln in lines:
        evs = list(ln.events)
        if not evs: continue
        names = collections.Counter(e.name for e in evs)
        print("  LINE", repr(ln.name), len(evs), "events;", names.most_common(6))
