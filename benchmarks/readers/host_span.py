"""Host time inside the benchmark's spans of one name over the measured
window, in ms per unit of work (iteration or call):
args {"span": name}.  Host clock; the device may still be working."""


def read(run, args):
    inside = [end - start for name, start, end in run.spans
              if name == args["span"]]
    if not inside or not run.shape.get("units"):
        return None
    return sum(inside) / run.shape["units"] * 1e3
