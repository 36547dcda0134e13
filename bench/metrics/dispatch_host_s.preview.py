"""Host seconds per preview dispatching the plugin steps: the self time
of the ``plugin.<name>.process`` spans, less the transfers and compiles
inside them.  A jitted step returns before the device finishes."""
import spans


def read(run):
    return spans.per_request(
        run, lambda name: name.startswith("plugin.")
        and name.endswith(".process"), self_time=True)
