"""Device busy time a forward call, from the trace."""


def read(ctx):
    t = ctx["trace"]
    calls = sum(1 for n, s, _d in t["spans"] if n == "forward"
                and t["window"][0] <= s < t["window"][1])
    return 1e3 * t["busy_s"] / calls if calls else None
