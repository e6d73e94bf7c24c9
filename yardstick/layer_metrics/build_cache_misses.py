"""build_cache_misses (count): executables that set-up had to compile and
write to the persistent cache because it did not hold them
(`/jax/compilation_cache/cache_misses`), as the program's own listener
counted them: `build.cache.misses` of the pvar snapshot at the window's
begin (`yardstick/build_reduce.py`). 0 in a warm run; a run whose `setup_s`
stands out says here whether it compiled again. Beside it, for a person, the
cache's hits and the seconds its reads took and stood for, and the names of
anything built inside the window."""

from yardstick import build_reduce


def read(run):
    fam = build_reduce.family(run)
    if fam is None:
        return None
    cache = fam["cache"]
    run.row(f"persistent cache in set-up: hits {cache['hits']}  misses "
            f"{cache['misses']}  reads {cache['load_s']:.3f} s for "
            f"{cache['saved_s']:.3f} s of compiles saved")
    late = build_reduce.built_in_window(run)
    if late:
        run.row("built INSIDE the window (function, phase, events, seconds): "
                + "  ".join(f"{name} {phase} x{n} {s:.3f}"
                            for name, phase, n, s in late))
    return int(cache["misses"])
