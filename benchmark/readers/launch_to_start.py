"""Mean wait of a dispatched program in the device's queue, ms: from the
end of the ``span`` annotation that launched it (the host has enqueued the
program and gone on) to the start of its module on the device, over the
dispatches of the traced slice. The nth launch of a module's name is
matched to its nth execution (xplane.match_launches)."""

import xplane


def read(run, params):
    if run.trace is None:
        return None
    trace = xplane.trace(run.trace_dir)
    if trace is None:
        return None
    return xplane.launch_to_start_ms(trace["host"], trace["modules"],
                                     params["span"], params["module_prefix"])
