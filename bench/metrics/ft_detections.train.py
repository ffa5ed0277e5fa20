"""Detections the program's FT report counts per step on clean data (no
SEU is injected anywhere in a run), over the steps of the traced window:
each is a false alarm, and under the online policy a rewrite of an output
element by the checksum residual."""


def read(layer):
    if not layer.get("steps"):
        return None
    return layer["ft_detections"] / layer["steps"]
