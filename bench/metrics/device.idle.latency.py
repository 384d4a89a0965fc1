"""Device: 1 less the union of device-operation intervals over the traced
window (%)."""


def read(view):
    return view.device_idle()
