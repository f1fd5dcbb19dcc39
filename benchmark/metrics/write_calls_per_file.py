"""Write system calls of the process per file indexed (`/proc/self/io`,
summed over the timed passes): SQLite's page cache spilling inside the
indexer's and the identifier's large transactions shows here, and with
it the share of a pass that the host's system-call cost sets."""


def read(ctx):
    calls = [p.get("io", {}).get("syscw") for p in ctx["passes"]]
    files = sum(p["files"] for p in ctx["passes"])
    if None in calls or not files:
        return None
    return sum(calls) / files
