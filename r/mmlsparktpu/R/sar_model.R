#' SARModel (Model)
#'
#' Scoring: affinity (U×I) @ similarity (I×I), top-k via `topk.top_k_rows` (reference SARModel.scala:95-130 BlockMatrix multiply + top-k udf).
#'
#' @param x a data.frame or tpu_table
#' @param user_col indexed user id column
#' @param item_col indexed item id column
#' @param prediction_col predicted affinity column
#' @export
ml_sar_model <- function(x, user_col = "user", item_col = "item", prediction_col = "prediction")
{
  params <- list()
  if (!is.null(user_col)) params$user_col <- as.character(user_col)
  if (!is.null(item_col)) params$item_col <- as.character(item_col)
  if (!is.null(prediction_col)) params$prediction_col <- as.character(prediction_col)
  .tpu_apply_stage("mmlspark_tpu.recommendation.sar.SARModel", params, x, is_estimator = FALSE)
}
