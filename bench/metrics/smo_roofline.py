"""Share of its roofline that the Pallas SMO kernel reached in the traced
window: the least seconds the chip could take for the window's real row
visits (4 r FLOPs and (r + 7) * 4 bytes each, `bench/work.py`), over the
device seconds of the kernel's program (`jit_smo_epoch_pallas`)."""

from bench import work

PROGRAM = "smo_epoch_pallas"


def read(run):
    if run.trace is None:
        return None
    device_s = sum(s for name, s in run.trace["modules"].items()
                   if PROGRAM in name)
    visits = [(r.rank, r.coord_visits) for r in run.jobs if r.coord_visits]
    if device_s <= 0 or not visits:
        return None
    least, bounds = 0.0, set()
    for r, v in visits:
        t, bound = work.roofline_seconds(work.smo_flops(r, v),
                                         work.smo_bytes(r, v), run.peak)
        least += t
        bounds.add(bound)
    run.log(f"smo_roofline: {least:.6g} s least ({'/'.join(sorted(bounds))}"
            f" bound) over {device_s:.6g} s of kernel device time")
    return 100.0 * least / device_s
