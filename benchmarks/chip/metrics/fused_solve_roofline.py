"""The fleet solve's share of the HBM roofline: the compulsory bytes of a
round (``roofline.fused_solve_bytes``) at the chip's peak bandwidth, over
the device time of one execution of the jitted ``solve_joint_fused``
program.  The solve is bound by memory, not by operations."""
import roofline

PROGRAM = "jit_solve_joint_fused"


def read(run):
    secs, n = run.trace.program_seconds(PROGRAM)
    if n == 0 or secs <= 0:
        return None
    nbytes = roofline.fused_solve_bytes(run.facts["n_devices"])
    return roofline.roofline_share(nbytes, secs / n,
                                   run.peaks()["hbm_bytes_per_s"])
