"""Share of device slots held per engine iteration, from the lifecycle's
slot-iteration counter over the window."""


def read(w):
    it = w.delta("iterations")
    if it <= 0:
        return None
    return 100.0 * w.delta("device_slot_iterations") / it / w.engine["device_slots"]
