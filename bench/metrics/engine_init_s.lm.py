"""engine_init_s.lm: seconds of the continuous engine's ``init_slots``
(its ``engine_init`` span, waited for) in set-up, from the engine's
``init_s`` counter.  The counter exists only where the recorder was on
when the engine was built."""


def read(ctx):
    return ctx.counters.get("engine_init_s")
