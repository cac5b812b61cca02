"""Set-up spent warming up (``kind.setup``: every program's first call and one
pass over the pool; on a cold cache the deep configuration's out-of-memory
attempt too), by ``run.py``'s clock either side of it.  ``first_call_s`` lies
inside it; set-up's other three parts are on the ``set-up:`` line."""


def read(run):
    parts = run.get("setup")
    return parts["warmup_s"] if parts else None
