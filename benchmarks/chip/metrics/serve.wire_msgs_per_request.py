"""Posts on the prefill and decode endpoints (``ServeTransport.counters``)
over the window, per request completed.  A count: it repeats exactly."""


def read(run):
    done = run.layer.get("completed")
    return run.layer["wire_posts"] / done if done else None
