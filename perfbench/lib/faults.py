"""Faults planted in the timed path, each of which the output check has to
catch: ``prepare`` functions for ``run.run_cell``, applied after set-up.
The CPU tests plant each at a tiny size; ``control.py --fault`` plants
one at the cell's own size on the chip."""
from __future__ import annotations

__all__ = ["FAULTS"]


def token_altered(run):
    """The last served token of every row moved to the next id."""
    engine = next(iter(run.platform._engines.values()))
    inner = engine.generate_many
    vocab = int(run.config["vocab_size"])

    def altered(gens, seed=0):
        out = inner(gens, seed=seed)
        for res in out:
            res.tokens[:, -1] = (res.tokens[:, -1] + 1) % vocab
        return out

    engine.generate_many = altered


def _wrap_batches(run, fn):
    inner = run.platform.run_batch

    def wrapped(tasks, n_paths, seed=0):
        return fn(inner, tasks, n_paths, seed)

    run.platform.run_batch = wrapped


def half_paths_hidden(run):
    """Half of each launch's paths left out; the mean over the rest is
    reported for the full count."""
    def fn(inner, tasks, n_paths, seed):
        recs = inner(tasks, [max(int(n) // 2, 1) for n in n_paths], seed=seed)
        return [r.__class__(r.platform, r.task_id, int(n), r.price, r.ci95,
                            r.latency) for r, n in zip(recs, n_paths)]

    _wrap_batches(run, fn)


def half_paths_reported(run):
    """Half of each launch's paths left out, and the count reported as it
    ran."""
    def fn(inner, tasks, n_paths, seed):
        return inner(tasks, [max(int(n) // 2, 1) for n in n_paths], seed=seed)

    _wrap_batches(run, fn)


def prices_moved(run):
    """Every price moved by a fifth of its CI where the platform makes it."""
    def fn(inner, tasks, n_paths, seed):
        return [r.__class__(r.platform, r.task_id, r.n_paths,
                            r.price + 0.2 * r.ci95, r.ci95, r.latency)
                for r in inner(tasks, n_paths, seed=seed)]

    _wrap_batches(run, fn)


#: name -> (runner kind, prepare)
FAULTS = {
    "token_altered": ("lm", token_altered),
    "half_paths_hidden": ("pricing", half_paths_hidden),
    "half_paths_reported": ("pricing", half_paths_reported),
    "prices_moved": ("pricing", prices_moved),
}
