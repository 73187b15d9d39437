"""Mean duration of a kernel launch on the host, in ms: the ``launch`` span
of ``PallasBackend._launch`` covers operand upload, the call, the wait and
mask readback, and ``!= 0`` (``repro.core.trace``) - scan routes,
``core/scan.py``.  Nothing to read without program spans."""


def read(ctx):
    took = [s.end_ns - s.start_ns for s in getattr(ctx, "spans", None) or ()
            if s.name == "launch"]
    return 1e-6 * sum(took) / len(took) if took else None
