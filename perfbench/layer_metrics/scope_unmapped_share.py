"""The reduction by scope's own health: share (%) of the device's busy time
over the traced job that it could give to no scope path: operations whose
name no map of ``op_scopes()`` holds, names two compiled shapes place
differently, and programs no map names (plain ``jit``, a ``profiled_jit``
that fell back).  A refactor that blinds the reduction shows here."""

import scope_reduce


def read(artifacts):
    reduced = scope_reduce.for_artifacts(artifacts)
    if not reduced or not reduced["busy_s"]:
        return None
    return 100.0 * reduced["unmapped_s"] / reduced["busy_s"]
