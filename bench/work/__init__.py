"""Operations and bytes each kernel's call needs, from the call's true
shapes: live rows and live context lengths, never the padded ones. One
file per kernel, named as the kernel is; ``model.py`` lists the calls a
decode step of a configuration makes."""
