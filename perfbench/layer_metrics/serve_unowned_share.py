"""L1 graph + compile: device seconds inside the traced window's serving
programs that no graph op and no scope of the programs' own (``sample``,
``step_io``, ``speculate``) owns, over all device seconds inside them, device
0: what the owner table cannot explain (instructions XLA made itself: layout
copies of parameters, asynchronous slices, the counters' copy), and the guard
that a later program or path does not lose its scopes (a program in the
window that the tables do not know is nobody's, whole).  On earlier lines:
the programs of the window, per kind of program the ms a program by owner
kind and part, and the six largest kinds of operation with whose they are."""

from perfbench.harness import serve_owners


def _largest(seconds, n=None):
    return sorted(seconds.items(), key=lambda kv: -kv[1])[:n]


def read(obs):
    got = serve_owners.read(obs)
    if got is None or not got["total"]:
        return None

    def say(text):
        print(f"[{obs.cell.name}] {text}", flush=True)

    say(f"device seconds inside the traced window's whole programs: "
        f"{got['total']:.6f}, nobody's {got['unowned']:.6f}; events and "
        f"median ms a program: " + ", ".join(
            f"{name} x{n} {ms:.3f}"
            for name, (n, ms) in sorted(got["programs"].items())))
    for kind in ("chunk", "token", "other"):
        n = sum(c for name, (c, _) in got["programs"].items()
                if serve_owners.program_kind(name) == kind)
        mine = {who + ("." + part if part else ""): v
                for (k, who, part), v in got["seconds"].items() if k == kind}
        if n and mine:
            say(f"by owner, ms a {kind} program over {n} "
                f"({1e3 * sum(mine.values()) / n:.3f} in all): " + ", ".join(
                    f"{who} {1e3 * v / n:.3f}" for who, v in _largest(mine)))
    kinds = sorted(got["by_kind"].items(),
                   key=lambda kv: -sum(kv[1].values()))[:6]
    say("the largest kinds of operation and whose they are, s: " + "; ".join(
        f"{kind} {sum(who.values()):.4f} = " + " + ".join(
            f"{name} {v:.4f}" for name, v in _largest(who, 8))
        for kind, who in kinds))
    return 100.0 * got["unowned"] / got["total"]
