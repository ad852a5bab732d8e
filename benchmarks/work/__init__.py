"""Operations and bytes of a step or a kernel, from its shapes.  Pure
functions of plain numbers; nothing here runs on a device."""
