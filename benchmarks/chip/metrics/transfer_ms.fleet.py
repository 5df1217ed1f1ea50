"""Host runtime time of the copies between host and device per step, in
ms: from the trace's host events, the layout conversions of the upload
(``XlaLinearize``) and of the read back (``XlaDelinearize``) and the
copies themselves, from issue to completion."""

EVENTS = ("XlaLinearize", "XlaDelinearize",
          "tpu::System::TransferToDevice=>IssueEvent=>Done",
          "tpu::System::TransferFromDevice=>IssueEvent=>Done")


def read(run):
    secs, n = run.trace.host_seconds(EVENTS)
    steps = run.facts.get("steps", 0)
    if n == 0 or not steps:
        return None
    return 1e3 * secs / steps
