"""Device milliseconds in ``pipe.send`` per GB that chip puts on the
pipeline's two rings, on the chip with the most send time: 1000 over it
is the rate the sends reach in GB/s.  Far above a link's own time per GB,
the sends wait for the peer's tick rather than transfer.  The bytes are
the program's static count (``runtime.train_step_counters``) of the
cell's step, built as ``drivers/train.py`` builds it; the time is the
profiler trace's (``scopes.py``)."""
from benchmarks.chip import scopes


def counters(cell_name: str):
    """The program's static counts of the cell's train step, or ``None``
    where the program has no such counter."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AbstractMesh

    from benchmarks.chip import cells
    from benchmarks.chip.drivers.common import program_config
    from repro.pipeline import runtime as RT
    from repro.pipeline import stage as ST
    if not hasattr(RT, "train_step_counters"):
        return None
    cell = cells.resolve(cell_name)
    tr = cell.traffic
    cfg = program_config(cell.config, cell.chips)
    mesh = AbstractMesh((1, cell.chips, 1), ("data", "stage", "tensor"))
    pcfg = RT.PipelineConfig(
        n_microbatches=tr["microbatches"], schedule=cfg.schedule,
        remat="stage", mem_limit=cfg.mem_limit, runtime=cfg.runtime,
        grad_sync="auto", ar_groups=1)
    rows = jax.ShapeDtypeStruct((tr["global_batch"], tr["seq_len"]),
                                jnp.int32)
    return RT.train_step_counters(cfg, mesh, ST.plan_stages(cfg), pcfg,
                                  dict(tokens=rows, labels=rows))


def read(run):
    r = scopes.of_run(run)
    if r is None or run.steps == 0:
        return None
    s = [c.get("pipe.send") or 0.0 for c in r["scope_s_per_chip"]]
    if not any(s):
        return None
    c = counters(r["cell"])
    if not c or not c["send_bytes_per_step"]:
        return None
    gb = c["send_bytes_per_step"] * run.steps / 1e9
    return 1e3 * max(s) / gb
