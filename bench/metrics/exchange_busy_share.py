"""``exchange_busy_share``: percent of the device's busy time in the
window spent in the mesh exchange's ``all_to_all`` ops, averaged over the
chips used as ``bench.trace`` averages (profiler trace).  XLA names such
an op ``all_to_all.<n>`` (its opcode is ``all-to-all``); either spelling
counts.  ``None`` where the trace holds no such op."""
import re

EXCHANGE = re.compile(r"all[-_]to[-_]all")


def read(run: dict):
    t = run["trace"]
    if not t or t["busy_s"] <= 0:
        return None
    ops = [s for name, s in t["ops"]
           if EXCHANGE.search(name.rsplit("/", 1)[-1])]
    return 100.0 * sum(ops) / t["busy_s"] if ops else None
