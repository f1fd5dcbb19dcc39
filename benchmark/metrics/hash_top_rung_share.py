"""`sd_cas_dispatch_rows_total{chunks,rung}`: of the filled rows (the
messages, not the pad rows) of the hash batches handed to the device,
the share dispatched at the pad ladder's last rung, the widest program
of a bucket: 1,024 rows a device, so 1,024 x the device count in the
array (a part too small to shard never reaches a rung that wide). None
on a program without the counter."""

HEAD = "sd_cas_dispatch_rows_total{"


def rows_by_rung(counters: dict) -> dict[int, float]:
    out: dict[int, float] = {}
    for key, rows in counters.items():
        if key.startswith(HEAD):
            labels = dict(kv.split("=") for kv in key[len(HEAD):-1].split(","))
            rung = int(labels["rung"])
            out[rung] = out.get(rung, 0.0) + rows
    return out


def read(ctx):
    by_rung = rows_by_rung(ctx["counters"])
    rows = sum(by_rung.values())
    if not rows:
        return None
    from spacedrive_tpu.ops import cas

    top = cas.batch_ladder(ctx["device"]["count"])[-1]
    return 100.0 * by_rung.get(top, 0.0) / rows
