"""Host seconds per scan in the loader's set-up
(``plugin.synthetic_tomo_loader.setup``): the phantom, the counts made
on the device and copied back, the truth volume."""
import spans


def read(run):
    return spans.per_request(run, "plugin.synthetic_tomo_loader.setup")
