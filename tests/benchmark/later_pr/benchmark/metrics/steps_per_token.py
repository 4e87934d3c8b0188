def read(run):
    c = run["counters"]
    return c["engine_steps"] / c["tokens"] if c.get("tokens") else None
