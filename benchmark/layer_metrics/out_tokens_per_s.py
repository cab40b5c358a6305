"""Output tokens that reached the client inside the window, over the
window; every request, finished or not: the capacity of a cell above the
knee, where the engine's queue is never empty.  A reading on the host's
clock at the client, kept per layer because its runs spread too widely to
hold a bound (PERF.md section 2); it is the end-to-end metric of such a
cell as soon as they do not."""


def read(run):
    if run.get("kind") != "serving":
        return None
    lo, hi = run["window"]
    return sum(1 for r in run["records"] for t in r["times"] if lo <= t < hi) / run["seconds"]
