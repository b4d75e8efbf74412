"""The collapse's share of the chip-to-chip interconnect's roofline, in
percent: the least bytes the window's collapses must pass through one
chip's links (``collective_work.least_ici_bytes`` of each ``plane.collapse``
span's ``devices`` and ``state_bytes``) at the chip's ICI peak
(``ici_peaks.json``), over the first device's time in every operation of
the collapse program (``collective_work.program_s``: the collectives and
the merge's work between them).  The program's instructions come from its
compiled HLO text, which the driver records as ``collapse_hlo``; a program
or driver without it gives nothing to read."""
import collective_work as cw
import program_spans as ps


def read(run):
    t = run["trace"]
    program = (run.get("record") or {}).get("collapse_hlo")
    if t is None or run["peak"] is None or program is None:
        return None
    recs = ps.window_records(run)
    if recs is None:
        return None
    nbytes = sum(cw.least_ici_bytes(r.counts["devices"], r.counts["state_bytes"])
                 for r in recs if r.name == "plane.collapse")
    if nbytes <= 0:
        return None
    seconds = cw.program_s(t["op_s"], program())
    if seconds <= 0:
        return None
    import jax

    peak = cw.ici_peak(jax.devices()[0].device_kind)
    return 100.0 * nbytes / peak / seconds
