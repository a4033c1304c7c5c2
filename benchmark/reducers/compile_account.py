"""What the program traced, lowered and compiled since the process began,
from its own account by program name (``deeperspeed_tpu.monitor.
compile_account()``): the count of lowerings (``what: lowered``) or the
seconds of tracing, lowering and compiling or loading (``what: seconds``)
of the programs whose name starts with ``prefix``. The reference's
programs, compiled later in the same process, carry other names. A
program without the account, as before PR 24, leaves the metric out; an
account that holds no program of the prefix is an error. With ``note``
the table by name is printed, then the row of the one-primitive programs
and the most lowered of the rest (jnp's own jitted helpers, the
benchmark's weights, and the reference's programs where it has run)."""

from .. import program_spans as ps
from .. import trace as tr

EAGER_ROW = "eager"
OTHERS_SHOWN = 6
PHASES = ("trace", "lower", "compile")


def read(run, params):
    acc = ps.compile_account()
    if acc is None:
        return None
    prefix = params["prefix"]
    mine = {n: row for n, row in acc.items() if n.startswith(prefix)}
    if not any("lower" in row for row in mine.values()):
        raise tr.TraceError(
            f"the compile account holds no lowered program {prefix}*: "
            f"{sorted(acc)}")

    def cell(row, phase, key):
        return row.get(phase, {}).get(key, 0)

    def seconds(row):
        return sum(cell(row, ph, "seconds") for ph in PHASES)

    if params.get("note"):
        rows = sorted(mine.items(), key=lambda kv: -seconds(kv[1]))
        rows += [(n, r) for n, r in acc.items() if n == EAGER_ROW]
        rest = sorted(((n, r) for n, r in acc.items()
                       if n not in mine and n != EAGER_ROW and "lower" in r),
                      key=lambda kv: -cell(kv[1], "lower", "count"))
        rows += [("other " + n, r) for n, r in rest[:OTHERS_SHOWN]]
        run["notes"].append(
            "compile account (program: lowered, trace + lower + compile s): "
            + "; ".join(
                f"{n}: {cell(r, 'lower', 'count')}, "
                + " + ".join(f"{cell(r, ph, 'seconds'):.3f}" for ph in PHASES)
                for n, r in rows))
    if params["what"] == "lowered":
        return sum(cell(r, "lower", "count") for r in mine.values())
    return sum(seconds(r) for r in mine.values())
