"""Launch layer: meshes, the compile cache, HLO cost and roofline
reading, and the transform-service driver."""
